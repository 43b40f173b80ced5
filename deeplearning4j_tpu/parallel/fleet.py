"""Fleet training: vmapped model populations through ONE compiled step.

ROADMAP item 5(a) — the "millions of users" *training* story: a stacked
population of M same-architecture members (per-user fine-tunes,
hyperparameter sweeps, RL populations) whose params / updater state /
RNG keys carry a leading population axis, trained by one ``jax.vmap``-ed
step core under a single ``jit``. Whole-graph compilation makes batching
entire *programs* nearly free on TPU (arXiv:1810.09868); the population
axis is the third companion to the data axis (parallel/wrapper.py) and
the model axis (parallel/sharding.py) — and unlike either, it costs ONE
compile for any M.

The load-bearing contracts:

- **Bitwise member parity.** Member k of a fleet is bit-identical to the
  same model trained solo with the same RNG stream: member init replays
  ``MultiLayerNetwork.init(member_seeds[k])`` exactly, the per-member
  stream key is carried IN-GRAPH and split exactly like the solo fit
  path splits its host ``Random`` (``new_key, sub = split(key)`` per
  step), and the step body IS the solo ``train_step.make_core`` — vmapped,
  never reimplemented. ``solo_twin(k)`` builds the comparator.
- **One compile, ever.** Telemetry, per-member hyperparameters, cull,
  spawn, and NaN isolation are all shape-stable data: the alive mask and
  hyper scalars are traced inputs, cull/spawn rewrite state slices with
  index-free ``where``/multiply forms, so nothing retraces
  (``trace/fleet_step`` stays 1; fleet-smoke arms
  ``tracecheck.steady_state`` over a cull+spawn drill to prove it).
  Known cost: the alive-freeze ``lax.cond`` keeps the pre-step state
  alive as a branch operand, so XLA cannot donate the stacked
  params/states/updater buffers into the step (the "donated buffers
  were not usable" warning at trace time) — peak memory is ~2x the
  stacked state during a dispatch, the price of bitwise member parity
  (see ``_build_fleet_step``).
- **Per-member telemetry, one sync per window.** The PR-2 aux pytree
  gains a leading member axis under vmap; the trainer buffers the device
  pytrees and drains the whole fleet's window in ONE batched
  ``jax.device_get`` (``telemetry/drain``), feeding storage sinks,
  per-member early-stop, and the NaN-cull reporter.
- **Per-member NaN isolation.** With a ``NanSentinelListener("skip")``
  the in-graph nan guard runs PER MEMBER under vmap: a poisoned member
  carries its pre-NaN state forward while the other M-1 updates land.
  Policy ``"cull"`` additionally flips that member's alive bit in-graph
  (event ``fleet/nan_cull``) — permanent isolation, zero retraces.
- **Checkpoint slicing.** ``save_member(k)`` commits member k as an
  ordinary solo checkpoint through the PR-3 atomic machinery (manifest
  entry tagged with ``fleet`` metadata); restoring it into a solo model
  is bit-exact INCLUDING the RNG stream, so the solo continuation
  reproduces the fleet member's future bit-for-bit. ``save()`` commits
  the whole stacked state (+ alive mask / keys / hyper in resume.json)
  and ``restore()`` resumes it exactly — kill+resume parity over the
  stacked state rides the same machinery as PR-4.

Serving handoff: ``export_member(best)``/``save_member(best)`` feed
PR-11's ``ServingEngine.publish_checkpoint`` — a fleet-trained member
canaries onto a live engine with zero recompiles (the AOT executables
take params as arguments).
"""

from __future__ import annotations

import copy
import logging
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..common import flightrec, xprof
from ..common.profiler import OpProfiler
from ..data import pipeline as _pipe
from ..nn.train_step import make_core
from ..optimize.telemetry import config_for

logger = logging.getLogger("deeplearning4j_tpu")

#: hyperparameters sweepable per member through the one compiled step
SWEEPABLE = ("lr", "l2", "dropout")


_fold_in = jax.jit(jax.random.fold_in)


class FleetEarlyStop:
    """Per-member early stopping driven from the telemetry bus: a member
    whose loss has not improved by ``min_delta`` for ``patience``
    consecutive TRAINED steps is culled (its slice freezes in-graph; the
    rest of the fleet keeps training, nothing retraces). Decisions run at
    drain boundaries on the batched window readback — the hot loop never
    syncs. A ``spawn`` resets the member's best/staleness
    (:meth:`member_spawned`), so a respawned member gets a fresh
    patience window instead of inheriting its dead predecessor's. The
    ``EarlyStoppingTrainer``-loop-per-model replacement."""

    wants_telemetry = True

    def __init__(self, patience: int, min_delta: float = 0.0):
        if patience < 1:
            raise ValueError(f"patience must be >= 1, got {patience}")
        self.patience = int(patience)
        self.min_delta = float(min_delta)
        self._best: Optional[np.ndarray] = None
        self._stale: Optional[np.ndarray] = None

    def member_spawned(self, member: int) -> None:
        """Forget a re-initialized member's history (FleetTrainer.spawn
        notifies every listener exposing this)."""
        if self._best is not None:
            self._best[int(member)] = np.inf
            self._stale[int(member)] = 0

    def decide(self, losses: np.ndarray, alive: np.ndarray) -> List[int]:
        """``losses``: [W, M] drained window; ``alive``: [M] current mask.
        Returns members to cull (alive ones whose staleness exceeded
        patience within this window)."""
        W, M = losses.shape
        if self._best is None:
            self._best = np.full(M, np.inf)
            self._stale = np.zeros(M, np.int64)
        out: List[int] = []
        for w in range(W):
            improved = losses[w] < self._best - self.min_delta
            self._best = np.where(improved, losses[w], self._best)
            self._stale = np.where(improved, 0, self._stale + 1)
        for m in range(M):
            if alive[m] and self._stale[m] > self.patience:
                out.append(m)
        return out

    # exact-resume support (rides the fleet checkpoint's listener_state)
    def state_dict(self) -> Dict[str, Any]:
        return {"best": None if self._best is None else self._best.tolist(),
                "stale": None if self._stale is None
                else self._stale.tolist()}

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self._best = (None if state.get("best") is None
                      else np.asarray(state["best"], np.float64))
        self._stale = (None if state.get("stale") is None
                       else np.asarray(state["stale"], np.int64))


class FleetStatsSink:
    """Drains per-member fleet telemetry into a ``StatsStorage`` backend
    (in-memory / JSONL / TensorBoard — the same SPI ``TelemetrySink``
    feeds). Emitted per drained iteration and member: ``fleet/loss/m<i>``,
    ``fleet/grad_norm/m<i>`` (the member's global gradient norm),
    ``fleet/alive/m<i>``, and ``fleet/nonfinite/m<i>`` when non-zero.
    Host cost is zero beyond the trainer's one batched window readback —
    this sink only fans the already-host values out."""

    wants_telemetry = True

    def __init__(self, storage, session_id: str = ""):
        self.storage = storage
        self.session = session_id

    def fleet_window(self, fleet: "FleetTrainer", iters: Sequence[int],
                     window: List[Dict[str, np.ndarray]]) -> None:
        put = self.storage.put_scalar
        for it, aux in zip(iters, window):
            loss = np.asarray(aux["loss"])
            gnorm = np.sqrt(np.sum(np.square(np.asarray(aux["grad_norm"],
                                                        np.float64)),
                                   axis=-1))
            alive = np.asarray(aux["alive"])
            nf = np.asarray(aux["nonfinite"])
            for m in range(fleet.n_members):
                put(self.session, f"fleet/loss/m{m}", it, float(loss[m]))
                put(self.session, f"fleet/grad_norm/m{m}", it,
                    float(gnorm[m]))
                put(self.session, f"fleet/alive/m{m}", it, int(alive[m]))
                nfm = int(np.sum(nf[m]))
                if nfm:
                    put(self.session, f"fleet/nonfinite/m{m}", it, nfm)


def _normalize_grid(grid) -> Dict[str, np.ndarray]:
    """Sweep grid → {field: float64 [M]}. Accepts a dict of equal-length
    lists (zipped — one member per row) or a list of per-member dicts
    (every dict must name the same fields)."""
    if isinstance(grid, dict):
        fields = dict(grid)
    elif isinstance(grid, (list, tuple)):
        if not grid:
            raise ValueError("empty sweep grid")
        keys = set(grid[0])
        if any(set(g) != keys for g in grid):
            raise ValueError("every sweep-grid row must name the same "
                             "hyperparameters")
        fields = {k: [g[k] for g in grid] for k in keys}
    else:
        raise TypeError(f"grid must be a dict of lists or a list of "
                        f"dicts, got {type(grid).__name__}")
    unknown = sorted(set(fields) - set(SWEEPABLE))
    if unknown:
        raise ValueError(f"unknown sweep field(s) {unknown}; sweepable: "
                         f"{list(SWEEPABLE)}")
    sizes = {len(v) for v in fields.values()}
    if len(sizes) != 1:
        raise ValueError(f"sweep-grid fields disagree on member count: "
                         f"{ {k: len(v) for k, v in fields.items()} }")
    # float64 on purpose: weak-Python-float matching under x64 — a swept
    # value equal to the baked one stays bitwise identical to solo
    return {k: np.asarray(v, np.float64) for k, v in fields.items()}


class FleetTrainer:
    """Train M stacked same-architecture members through one vmapped,
    jitted step. ``model`` is the architecture template (an init()-ed
    ``MultiLayerNetwork``); the trainer owns it for tracing — its layer
    pure functions and ``train_step.make_core`` ARE the member step, so
    fleet numerics can never drift from solo numerics.

    Thread-shared by registry (graftlint SHARED_CLASSES): the training
    thread mutates carried state while sinks/serving read exports —
    every mutation holds ``_lock``.
    """

    def __init__(self, model, n_members: Optional[int] = None, *,
                 hyper=None, seed: Optional[int] = None,
                 member_seeds: Optional[Sequence[int]] = None,
                 drain_every_n: int = 10):
        model._check_init()
        self._lock = threading.Lock()
        self.model = model
        self._hyper_np = _normalize_grid(hyper) if hyper else None
        counts = set()
        if n_members is not None:
            counts.add(int(n_members))
        if member_seeds is not None:
            counts.add(len(member_seeds))
        if self._hyper_np:
            counts.add(len(next(iter(self._hyper_np.values()))))
        if len(counts) != 1:
            raise ValueError(
                f"member count ambiguous or missing: n_members/"
                f"member_seeds/hyper imply {sorted(counts)}")
        M = counts.pop()
        if M < 1:
            raise ValueError(f"need at least one member, got {M}")
        self.n_members = M
        self._seed = int(seed if seed is not None
                         else model.conf.global_conf.seed)
        self.member_seeds = (list(member_seeds) if member_seeds is not None
                             else [self._seed + i for i in range(M)])
        # stacked state: member i's init replays MultiLayerNetwork.init
        # with member_seeds[i] exactly (the parity contract)
        per_member = [self._init_member(s) for s in self.member_seeds]
        self._params = jax.tree.map(lambda *xs: jnp.stack(xs),
                                    *[p for p, _ in per_member])
        self._states = jax.tree.map(lambda *xs: jnp.stack(xs),
                                    *[s for _, s in per_member])
        self._updater_state = \
            model.conf.global_conf.updater.init(self._params)
        # per-member RNG streams, carried in-graph: fold_in(member) off
        # one base key; solo_twin() hands the same stream to a solo model
        base = jax.random.PRNGKey(self._seed)
        self._keys = jnp.stack([jax.random.fold_in(base, i)
                                for i in range(M)])
        self._alive = jnp.ones((M,), jnp.int32)
        self._alive_np = np.ones(M, np.int64)    # host mirror (reporting)
        self._hyper = (None if self._hyper_np is None else
                       {k: jnp.asarray(v)
                        for k, v in self._hyper_np.items()})
        self._iteration = 0
        self._epoch = 0
        self._score_dev = None
        self._listeners: List[Any] = []
        self._tele = None
        self._fit_step = None
        self._drain_every = max(1, int(drain_every_n))
        self._aux_buf: List[tuple] = []
        self._last_losses: Optional[np.ndarray] = None
        self._infer_fn = None
        OpProfiler.get().gauge("fleet/members", M)

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_sweep(cls, base_model, grid, *, seed: Optional[int] = None,
                   same_init: bool = True,
                   drain_every_n: int = 10) -> "FleetTrainer":
        """Hyperparameter-sweep constructor: one member per grid row, the
        whole sweep one trace. ``same_init=True`` (the usual sweep
        methodology) gives every member the SAME initial params — the
        sweep isolates the hyperparameter axis; False re-inits per member
        (seed+i). Sweepable fields: ``lr``, ``l2``, ``dropout``."""
        hyper = _normalize_grid(grid)
        M = len(next(iter(hyper.values())))
        seed = int(seed if seed is not None
                   else base_model.conf.global_conf.seed)
        seeds = [seed] * M if same_init else [seed + i for i in range(M)]
        return cls(base_model, M, hyper=hyper, seed=seed,
                   member_seeds=seeds, drain_every_n=drain_every_n)

    # -- plumbing ----------------------------------------------------------
    @property
    def conf(self):
        """The template's configuration — makes the trainer duck-type as
        a model for the PR-3 checkpoint machinery (snapshot /
        load_state_entries work on the stacked trees unchanged)."""
        return self.model.conf

    def _check_init(self) -> None:    # checkpoint-machinery duck-typing
        pass

    def _init_member(self, seed: int):
        """Replay MultiLayerNetwork.init(seed) for one member (host-side;
        bitwise identical to the solo init by construction)."""
        conf = self.model.conf
        key = jax.random.PRNGKey(int(seed))
        dtype = jnp.dtype(conf.global_conf.dtype)
        params, states = [], []
        for layer in self.model.layers:
            key, sub = jax.random.split(key)
            params.append(layer.init_params(sub, dtype)
                          if layer.has_params else {})
            states.append(layer.init_state())
        return params, states

    def member_stream_state(self, member: int) -> Dict[str, Any]:
        """The RNG-stream state a SOLO run must start from to replay
        member ``member``'s training stream (``Random.set_state``
        payload)."""
        base = jax.random.PRNGKey(self._seed)
        return {"seed": self._seed,
                "key": jax.random.fold_in(base, int(member))}

    def solo_twin(self, member: int):
        """A fresh solo model positioned to train bit-identically to
        member ``member``: same init seed, and the calling thread's RNG
        stream moved onto the member's fold_in key. The parity-gate
        comparator (fleet-smoke, tests)."""
        from ..ndarray.rng import get_random
        from ..nn.multilayer import MultiLayerNetwork

        net = MultiLayerNetwork(copy.deepcopy(self.model.conf))
        net.init(self.member_seeds[int(member)])
        get_random().set_state(self.member_stream_state(member))
        return net

    def set_listeners(self, *listeners) -> None:
        """Attach listeners. Telemetry-wanting listeners (TelemetrySink
        protocol attributes) switch the step to carry the per-member aux
        pytree — one rebuild, still one trace. ``NanSentinelListener``
        carries the per-member NaN policy (``"skip"`` = transient
        isolation, ``"cull"`` = permanent); :class:`FleetEarlyStop`
        culls from the drained window; objects exposing ``fleet_window``
        (:class:`FleetStatsSink`) receive every drained window."""
        cfg = config_for(list(listeners))
        with self._lock:
            self._listeners = list(listeners)
            if cfg != self._tele:
                self._tele = cfg
                self._fit_step = None

    # -- the one compiled step --------------------------------------------
    def _build_fleet_step(self):
        # the member body IS the solo step core (parity by construction);
        # telemetry is a build-time property exactly as in the solo paths
        tele = self._tele
        core = make_core(self.model, tele)
        member_cull = bool(tele and tele.member_cull)
        with_hyper = self._hyper is not None

        def member(p, s, u, key, x_m, y_m, hyp, it):
            new_key, sub = jax.random.split(key)
            out = core(p, s, u, (x_m, y_m, None, None), sub, it, None,
                       hyper=hyp)
            if tele is None:
                new_p, new_s, new_u, loss = out
                aux = None
            else:
                new_p, new_s, new_u, loss, aux = out
            return new_p, new_s, new_u, new_key, loss, aux

        if with_hyper:
            vmapped = jax.vmap(member, in_axes=(0, 0, 0, 0, 0, 0, 0, None))
        else:
            def member_nohyp(p, s, u, key, x_m, y_m, it):
                return member(p, s, u, key, x_m, y_m, None, it)

            vmapped = jax.vmap(member_nohyp,
                               in_axes=(0, 0, 0, 0, 0, 0, None))

        def fleet_step(params, states, upd, keys, alive, x, y, hyper, it):
            OpProfiler.get().count("trace/fleet_step")
            if with_hyper:
                new_p, new_s, new_u, new_keys, losses, aux = vmapped(
                    params, states, upd, keys, x, y, hyper, it)
            else:
                new_p, new_s, new_u, new_keys, losses, aux = vmapped(
                    params, states, upd, keys, x, y, it)
            ok = alive > 0

            # The alive-mask freeze lives INSIDE a lax.cond on purpose:
            # XLA does not fuse across the conditional boundary, so the
            # all-alive path returns the vmapped core's outputs with
            # their fusion layout untouched — a bare jnp.where here gets
            # its producers DUPLICATED into the select fusion and
            # re-contracted, which cost the Adam/Nesterovs family ~1 ulp
            # per step against the solo program (measured; Sgd survived).
            # With the cond, member-vs-solo parity is bitwise for every
            # updater, culled or not.
            def frozen(args):
                (n_p, n_s, n_u, n_k), (o_p, o_s, o_u, o_k) = args

                def keep(n, o):
                    return jnp.where(
                        ok.reshape((ok.shape[0],) + (1,) * (n.ndim - 1)),
                        n, o)

                return (jax.tree.map(keep, n_p, o_p),
                        jax.tree.map(keep, n_s, o_s),
                        jax.tree.map(keep, n_u, o_u), keep(n_k, o_k))

            def live(args):
                return args[0]

            new_p, new_s, new_u, new_keys = jax.lax.cond(
                jnp.all(ok), live, frozen,
                ((new_p, new_s, new_u, new_keys),
                 (params, states, upd, keys)))
            new_alive = alive
            if aux is not None:
                if member_cull:
                    # per-member NaN isolation, permanent flavor: the nan
                    # guard already dropped the poisoned member's update
                    # in-graph (per member, under vmap); flipping its
                    # alive bit here freezes it for good
                    new_alive = alive * (1 - aux["skipped"])
                aux = dict(aux)
                aux["alive"] = new_alive
            return new_p, new_s, new_u, new_keys, new_alive, losses, aux

        # No donation on purpose: the freeze cond keeps the pre-step
        # param/state/updater buffers alive as branch operands (XLA
        # reports them unusable anyway), and the SMALL carried buffers
        # (keys, alive) WOULD donate — deleting arrays a concurrent
        # cull()/alive_mask()/_member_rng_state() may still be reading.
        return xprof.register_jit("fleet/step", jax.jit(fleet_step))

    # -- training ----------------------------------------------------------
    def step(self, x, y, per_member: bool = False):
        """One fleet step. ``per_member=True``: ``x``/``y`` carry a
        leading [M] member axis (per-user data); otherwise the one batch
        is broadcast fleet-wide (sweeps, populations on shared data).
        Returns the per-member DEVICE loss vector [M] (no host sync)."""
        xv = jnp.asarray(x)
        yv = jnp.asarray(y)
        if not per_member:
            xv = jnp.broadcast_to(xv, (self.n_members,) + xv.shape)
            yv = jnp.broadcast_to(yv, (self.n_members,) + yv.shape)
        elif xv.shape[0] != self.n_members:
            raise ValueError(
                f"per_member batch leading axis {xv.shape[0]} != fleet "
                f"size {self.n_members}")
        prof = OpProfiler.get()
        # the lock spans capture -> dispatch -> write-back: a concurrent
        # cull/spawn (the controller thread) can never interleave with an
        # in-flight step and have its state rewrite silently overwritten
        # by outputs derived from the pre-cull state. Dispatch is async
        # (the jit call returns once enqueued), so the hold is short.
        with self._lock:
            if self._fit_step is None:
                self._fit_step = self._build_fleet_step()
            with prof.time_section("pipeline/dispatch"):
                out = self._fit_step(self._params, self._states,
                                     self._updater_state, self._keys,
                                     self._alive, xv, yv, self._hyper,
                                     jnp.asarray(self._iteration))
            new_p, new_s, new_u, new_keys, new_alive, losses, aux = out
            self._params, self._states, self._updater_state = \
                new_p, new_s, new_u
            self._keys, self._alive = new_keys, new_alive
            self._iteration += 1
            self._score_dev = losses
            it_done = self._iteration
        if aux is not None:
            self._note_aux(it_done, aux)
        return losses

    def fit(self, data, epochs: int = 1,
            batch_size: Optional[int] = None) -> None:
        """Train the whole fleet on a shared data stream: every DataSet
        batch is broadcast across the member axis and dispatched as ONE
        compiled step (per-member data goes through
        ``step(..., per_member=True)``). Batch shapes must stay stable
        (use the iterator's padding knobs) — the fleet compiles once."""
        for _ in range(max(1, epochs)):
            for ds in _pipe.iter_datasets(data, batch_size):
                self.step(jnp.asarray(ds.features.value),
                          jnp.asarray(ds.labels.value))
            with self._lock:
                self._epoch += 1
            self.drain()

    # -- telemetry bus (one device_get per drain window) -------------------
    def _note_aux(self, iteration: int, aux) -> None:
        # append under the lock: drain() swaps the buffer out under the
        # same lock (possibly from another thread — save(), best_member()
        # on a controller), and an unlocked append could land on the
        # already-captured window and silently vanish
        with self._lock:
            self._aux_buf.append((iteration, aux))
            full = len(self._aux_buf) >= self._drain_every
        if full:
            self.drain()

    def drain(self) -> None:
        """Flush the buffered telemetry window: ONE batched readback for
        the whole fleet, then fan out to sinks / NaN-cull reporting /
        early-stop decisions. The only host sync telemetry pays."""
        with self._lock:
            buf, self._aux_buf = self._aux_buf, []
            listeners = list(self._listeners)
        if not buf:
            return
        prof = OpProfiler.get()
        with prof.time_section("telemetry/drain"):
            host = jax.device_get([a for _, a in buf])
        prof.count("fleet/drains")
        iters = [it for it, _ in buf]
        alive_after = np.array(host[-1]["alive"], np.int64)
        # in-graph NaN culls surface here: a member alive before the
        # window whose skipped flag coincided with its alive bit dropping
        was_alive = self._alive_np.copy()
        for (it, _), aux in zip(buf, host):
            skipped = np.array(aux.get("skipped", 0))
            alive_now = np.array(aux["alive"], np.int64)
            if skipped.ndim == 0:
                continue
            for m in np.nonzero((skipped > 0) & (was_alive > 0)
                                & (alive_now == 0))[0]:
                flightrec.event("fleet/nan_cull", severity="warn",
                                member=int(m), iteration=int(it))
                prof.count("fleet/nan_culls")
                logger.warning(
                    "fleet: member %d produced non-finite gradients at "
                    "iteration %d; its alive bit was flipped in-graph "
                    "(other members unaffected)", int(m), int(it))
            was_alive = alive_now
        with self._lock:
            self._alive_np = alive_after
            losses = np.stack([np.array(a["loss"], np.float64)
                               for a in host])
            self._last_losses = losses[-1]
        prof.gauge("fleet/members", int(alive_after.sum()))
        for lst in listeners:
            win = getattr(lst, "fleet_window", None)
            if callable(win):
                win(self, iters, host)
            if isinstance(lst, FleetEarlyStop):
                for m in lst.decide(losses, alive_after):
                    self.cull(m, reason="early_stop")

    # -- lifecycle ---------------------------------------------------------
    def alive_mask(self) -> np.ndarray:
        """Host view of the alive mask. Synced on demand — authoritative
        including in-graph NaN culls the drain has not reported yet."""
        alive = np.asarray(self._alive, np.int64)
        with self._lock:
            self._alive_np = alive
        return alive.copy()

    def cull(self, member: int, reason: str = "cull") -> None:
        """Freeze member ``member``: its alive bit drops to 0 and every
        subsequent update is zeroed IN-GRAPH (``where`` against the
        carried state) — shape-stable, no retrace. The slice keeps its
        exact pre-cull bits (export/save still work)."""
        m = int(member)
        if not 0 <= m < self.n_members:
            raise ValueError(f"member {m} out of range [0, "
                             f"{self.n_members})")
        sel = np.zeros(self.n_members, np.int32)
        sel[m] = 1
        with self._lock:
            # index-free form: one compile for ANY member, ever
            self._alive = self._alive * jnp.asarray(1 - sel)
            self._alive_np = self._alive_np * (1 - sel.astype(np.int64))
            alive_now = int(self._alive_np.sum())
        OpProfiler.get().count("fleet/culls")
        OpProfiler.get().gauge("fleet/members", alive_now)
        flightrec.event("fleet/cull", severity="warn", member=m,
                        reason=reason)

    def spawn(self, member: int, params=None,
              seed: Optional[int] = None) -> None:
        """Re-initialize member ``member`` IN PLACE: fresh params (from
        ``seed``, default its original member seed — or an explicit solo
        param tree), zeroed updater state, a fresh fold_in stream key,
        alive bit back to 1. Index-free slice rewrite — no retrace.
        The member inherits the fleet-global iteration counter (updater
        bias correction continues from it; an exact solo replay of a
        spawned member therefore needs the same starting iteration)."""
        m = int(member)
        if not 0 <= m < self.n_members:
            raise ValueError(f"member {m} out of range [0, "
                             f"{self.n_members})")
        if params is None:
            params, states = self._init_member(
                self.member_seeds[m] if seed is None else int(seed))
        else:
            states = self._init_member(self.member_seeds[m])[1]
        sel = np.zeros(self.n_members, np.int32)
        sel[m] = 1
        sel_dev = jnp.asarray(sel)

        def put(stacked, value):
            mask = sel_dev.astype(bool).reshape(
                (self.n_members,) + (1,) * (stacked.ndim - 1))
            return jnp.where(mask, jnp.asarray(value,
                                               stacked.dtype)[None],
                             stacked)

        fresh_upd = self.model.conf.global_conf.updater.init(params)
        # through one jitted program with the index as an argument: an
        # eager fold_in converts the Python int anew, and that conversion
        # was seen to trace again inside a steady-state region
        new_key = _fold_in(
            jax.random.PRNGKey(self._seed if seed is None else int(seed)),
            np.uint32(m))
        with self._lock:
            self._params = jax.tree.map(put, self._params, params)
            self._states = jax.tree.map(put, self._states, states)
            self._updater_state = jax.tree.map(put, self._updater_state,
                                               fresh_upd)
            self._keys = put(self._keys, new_key)
            self._alive = jnp.maximum(self._alive, sel_dev)
            self._alive_np = np.maximum(self._alive_np,
                                        sel.astype(np.int64))
            alive_now = int(self._alive_np.sum())
            listeners = list(self._listeners)
        for lst in listeners:
            # early-stop (and anything else tracking per-member history)
            # must forget the dead predecessor, or the fresh member gets
            # culled again within one drain window
            cb = getattr(lst, "member_spawned", None)
            if callable(cb):
                cb(m)
        OpProfiler.get().count("fleet/spawns")
        OpProfiler.get().gauge("fleet/members", alive_now)
        flightrec.event("fleet/spawn", member=m,
                        seed=int(self.member_seeds[m]
                                 if seed is None else seed))

    def best_member(self) -> int:
        """The alive member with the lowest last-drained loss (requires a
        telemetry listener; drains any buffered window first)."""
        self.drain()
        with self._lock:
            losses = self._last_losses
            alive = self._alive_np.copy()
        if losses is None:
            raise RuntimeError("best_member needs telemetry: attach a "
                               "telemetry listener (set_listeners) and "
                               "train at least one step")
        masked = np.where(alive > 0, losses, np.inf)
        return int(np.argmin(masked))

    # -- member export / checkpoint slicing --------------------------------
    def export_member(self, member: int):
        """Slice member ``member`` out of the stacked state into a fresh
        SOLO ``MultiLayerNetwork`` (owning buffers — safe against the
        fleet step's donation), carrying params / layer states / updater
        state / iteration. The serving-handoff and solo-restore vehicle.
        """
        from ..nn.multilayer import MultiLayerNetwork

        m = int(member)
        if not 0 <= m < self.n_members:
            raise ValueError(f"member {m} out of range [0, "
                             f"{self.n_members})")
        net = MultiLayerNetwork(copy.deepcopy(self.model.conf))
        net.init(self.member_seeds[m])
        with self._lock:
            net._params = jax.tree.map(lambda a: jnp.array(a[m]),
                                       self._params)
            net._states = jax.tree.map(lambda a: jnp.array(a[m]),
                                       self._states)
            net._updater_state = jax.tree.map(lambda a: jnp.array(a[m]),
                                              self._updater_state)
            net._iteration = self._iteration
            net._epoch = self._epoch
        return net

    def _member_rng_state(self, member: int) -> Dict[str, Any]:
        """The member's CURRENT carried stream key as a Random state —
        what a solo continuation must resume from."""
        with self._lock:
            key = np.asarray(self._keys)[int(member)]
        return {"seed": self._seed, "key": key}

    def save_member(self, member: int, directory: str,
                    tag: Optional[str] = None, keep_last: int = 10) -> str:
        """Commit member ``member`` as an ordinary SOLO checkpoint through
        the PR-3 atomic machinery (tmp→fsync→rename→manifest), its
        manifest entry tagged with ``fleet`` metadata. The zip carries
        the member's CURRENT stream key, so
        ``restore_training_state(solo, path)`` resumes the member's
        exact future: the solo continuation is bit-identical to the
        member continuing inside the fleet."""
        from ..util.checkpoint import (commit_checkpoint,
                                       serialize_snapshot,
                                       snapshot_training_state)

        m = int(member)
        net = self.export_member(m)
        snap = snapshot_training_state(net,
                                       rng_state=self._member_rng_state(m))
        tag = tag if tag is not None else f"member{m}_it{snap['iteration']}"
        data = serialize_snapshot(snap)
        return commit_checkpoint(
            directory, tag, data, snap["iteration"], keep_last,
            state_dtype=snap.get("state_dtype"),
            fleet={"member": m, "members": self.n_members})

    def save(self, directory: str, tag: Optional[str] = None,
             keep_last: int = 3) -> str:
        """Commit the WHOLE stacked fleet atomically: the standard
        snapshot machinery over the stacked trees (the trainer
        duck-types as a model), plus the fleet extras — alive mask,
        per-member stream keys, hyper grid, member seeds — in
        resume.json. ``restore()`` resumes bit-exactly, alive mask
        included."""
        from ..util.checkpoint import (commit_checkpoint,
                                       serialize_snapshot,
                                       snapshot_training_state)

        self.drain()
        with self._lock:
            keys = np.asarray(self._keys)
            fleet_extra = {
                "members": self.n_members,
                "member_seeds": [int(s) for s in self.member_seeds],
                "seed": self._seed,
                "alive": [int(a) for a in np.asarray(self._alive)],
                "keys": keys.tolist(),
                "keys_dtype": str(keys.dtype),
                "hyper": (None if self._hyper_np is None else
                          {k: v.tolist()
                           for k, v in self._hyper_np.items()}),
            }
            listeners = list(self._listeners)
        snap = snapshot_training_state(self, listeners=listeners)
        snap["fleet"] = fleet_extra
        tag = tag if tag is not None else f"fleet_it{snap['iteration']}"
        data = serialize_snapshot(snap)
        return commit_checkpoint(
            directory, tag, data, snap["iteration"], keep_last,
            state_dtype=snap.get("state_dtype"),
            fleet={"members": self.n_members})

    def restore(self, path: str) -> None:
        """Resume a :meth:`save` checkpoint into this trainer (same
        architecture and member count): stacked params / states / updater
        state / counters through the standard restore path, then the
        fleet extras — alive mask, carried stream keys, hyper grid.
        Kill+resume is bit-exact, cull state included."""
        from ..util.checkpoint import (read_resume_state,
                                       restore_training_state)

        extra = read_resume_state(path).get("fleet")
        if not extra:
            raise ValueError(
                f"{path} is not a fleet checkpoint (no fleet extras in "
                f"resume.json); member checkpoints restore into a SOLO "
                f"model via restore_training_state")
        if int(extra["members"]) != self.n_members:
            raise ValueError(
                f"checkpoint has {extra['members']} members, trainer has "
                f"{self.n_members}")
        with self._lock:
            listeners = list(self._listeners)
        restore_training_state(self, path, listeners=listeners,
                               restore_rng=False)
        keys = np.asarray(extra["keys"],
                          dtype=extra.get("keys_dtype", "uint32"))
        with self._lock:
            self._keys = jnp.asarray(keys)
            self._alive = jnp.asarray(np.asarray(extra["alive"], np.int32))
            self._alive_np = np.asarray(extra["alive"], np.int64)
            self._seed = int(extra.get("seed", self._seed))
            self.member_seeds = [int(s) for s in extra["member_seeds"]]
            hyper = extra.get("hyper")
            self._hyper_np = (None if hyper is None else
                              {k: np.asarray(v, np.float64)
                               for k, v in hyper.items()})
            self._hyper = (None if self._hyper_np is None else
                           {k: jnp.asarray(v)
                            for k, v in self._hyper_np.items()})
            self._fit_step = None      # restored buffers replace donated
            self._aux_buf = []
        OpProfiler.get().gauge("fleet/members",
                               int(self._alive_np.sum()))

    # -- stacked inference (population hooks) ------------------------------
    def output(self, x, params=None, per_member: bool = True):
        """Vmapped inference over the fleet: ``x`` [M, B, ...] (or one
        shared batch with ``per_member=False``) → stacked outputs
        [M, B, ...]. ``params``/states default to the live fleet state;
        pass an explicit (params, states) pair for target-network-style
        frozen copies (rl.population). One trace, reused forever."""
        xv = jnp.asarray(x)
        if not per_member:
            xv = jnp.broadcast_to(xv, (self.n_members,) + xv.shape)
        with self._lock:
            if self._infer_fn is None:
                def infer(p, s, xin, key):
                    out, _ = self.model._forward(p, s, xin, False, key)
                    return out

                self._infer_fn = xprof.register_jit(
                    "fleet/infer",
                    jax.jit(jax.vmap(infer, in_axes=(0, 0, 0, None))))
            fn = self._infer_fn
            p, s = ((self._params, self._states) if params is None
                    else params)
        return fn(p, s, xv, jax.random.PRNGKey(0))

    def stacked_state(self):
        """Owning copies of the live (params, states) stacks — a frozen
        target-network snapshot for RL populations."""
        with self._lock:
            return (jax.tree.map(jnp.array, self._params),
                    jax.tree.map(jnp.array, self._states))
