"""Training history (reference org.nd4j.autodiff.listeners.records.History)."""

from __future__ import annotations

from typing import Dict, List, Optional


class History:
    """Epoch losses as ``fit`` recorded them: device scalars, so that
    ``fit`` waits for no step. They become Python floats the first time
    they are read, and stay floats."""

    def __init__(self) -> None:
        self._epoch_losses: List = []
        self._epochs: List[int] = []
        self._evaluations: Dict[str, List[float]] = {}

    def add_epoch(self, epoch: int, loss) -> None:
        self._epochs.append(epoch)
        self._epoch_losses.append(loss)

    def add_evaluation(self, name: str, value: float) -> None:
        self._evaluations.setdefault(name, []).append(value)

    def _read(self) -> List[float]:
        # the one place that waits for the device
        self._epoch_losses = [float(l) for l in self._epoch_losses]
        return self._epoch_losses

    def loss_curve(self) -> List[float]:
        return list(self._read())

    def final_loss(self) -> Optional[float]:
        return self._read()[-1] if self._epoch_losses else None

    def __repr__(self) -> str:
        return f"History(epochs={len(self._epochs)}, final_loss={self.final_loss()})"
