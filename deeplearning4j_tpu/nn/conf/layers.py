"""Layer configurations + their pure-function runtime.

Reference: dl4j-nn ``org.deeplearning4j.nn.conf.layers.*`` (the ~60 config
classes, SURVEY.md §2.3) merged with their runtime twins in
``org.deeplearning4j.nn.layers.**``. The reference splits config (Jackson
beans) from runtime (INDArray code); here each dataclass carries both: the
config fields plus ``init_params`` / ``apply`` pure functions that trace into
the one compiled train-step module. Param layouts follow the reference
ParamInitializers: dense W=[nIn,nOut], conv W=[out,in,kH,kW] (OIHW),
bias=[nOut].

Every ``apply`` is functional: (params, x, state, training, rng) -> (y, state)
where ``state`` is whatever a layer keeps between steps that is not a
parameter and takes no gradient: BatchNorm's running statistics,
``RoutedExpertsLayer``'s selection bias and accumulated expert load. The
networks hand a layer's state in and carry what it returns, whatever its
kind; ``init_state()`` is empty for a stateless layer.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.registry import get_op
from ..activations import activation_fn
from ..losses import ILossFunction, LossMCXENT, loss_from_name
from ..weights import init_weights
from .inputs import CNNInput, FFInput, InputType, RNNInput


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


# --- trace-time dropout-rate override (fleet hyperparameter sweeps) --------
#
# A vmapped model population (parallel.fleet) sweeps the INPUT-dropout
# rate per member by threading a traced scalar through the one compiled
# step. The rate cannot live on the layer dataclass (it is a Python float
# baked at trace time), so the fleet core installs the traced value here
# for the duration of its loss trace; ``_maybe_dropout`` picks it up.
# Per-thread (concurrent traces stay independent) and trace-time only —
# a compiled step never reads it again. The gate (is dropout configured
# at all?) stays on the layer's own Python float, so only layers that
# already drop out participate in the sweep.
_DROPOUT_OVERRIDE = threading.local()


@contextlib.contextmanager
def dropout_rate_override(rate):
    """Install a traced input-dropout RATE override for every
    dropout-configured layer traced inside the block. The value must be
    float64 (weak-Python-float matching under x64) for an override equal
    to the configured rate to be bitwise identical."""
    prev = getattr(_DROPOUT_OVERRIDE, "rate", None)
    _DROPOUT_OVERRIDE.rate = rate
    try:
        yield
    finally:
        _DROPOUT_OVERRIDE.rate = prev


@dataclass
class Layer:
    """Base layer config. Fields that default to None inherit the network's
    global defaults (NeuralNetConfiguration.Builder contract)."""

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    # Input dropout RATE (fraction dropped). None = inherit the builder's
    # global dropout; 0.0 = explicitly disabled.
    dropout: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None

    # filled by the builder
    n_in: Optional[int] = None
    # post-update weight projections (reference api.layers.constraint.*;
    # applied by the fit step after the updater)
    constraints: Optional[list] = None
    # training-time param perturbation (reference conf.weightnoise.*;
    # applied by the network before apply())
    weight_noise: Optional[Any] = None

    def set_input_type(self, input_type: InputType) -> InputType:
        """Infer nIn from the incoming type; return this layer's output type."""
        return input_type

    def init_params(self, key: jax.Array, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
        return {}

    def init_state(self) -> Dict[str, jnp.ndarray]:
        return {}

    def apply(self, params, x, state, training: bool, rng):
        raise NotImplementedError

    def _maybe_dropout(self, x, training: bool, rng):
        if training and self.dropout and self.dropout > 0.0:
            rate = getattr(_DROPOUT_OVERRIDE, "rate", None)
            if rate is None:
                rate = self.dropout
            return get_op("dropout").fn(x, rng, rate=rate)
        return x

    @property
    def has_params(self) -> bool:
        return True

    # -- what ComputationGraph reads of a layer beyond apply() (the
    # sequence layers of layers_seq.py use them; every default is "no") --
    #: apply()'s ``x`` is the tuple of the node's inputs, not the first
    multi_input = False
    #: leaves that stay in the storage dtype under ``compute_dtype``
    full_precision_params = ()

    def extra_outputs(self) -> Tuple[str, ...]:
        """Names of the tensors apply() returns after ``y``; another node
        reads one as ``"<node>.<name>"``."""
        return ()

    def extra_output_types(self, input_type) -> Tuple:
        return ()

    def borrowed_params(self) -> Dict[str, Tuple[str, str]]:
        """{own name: (node, leaf)} of leaves that belong to another
        node and are handed to apply() beside this layer's own."""
        return {}

    # -- per-timestep feature masking (reference: Layer.setMaskArray /
    # feedForwardMaskArray; SURVEY §5.7 masking row) --------------------
    def apply_masked(self, params, x, state, training, rng, fmask):
        """Forward with a [B, T] feature mask (1 = real step). Default:
        mask-oblivious layers ignore it; recurrent/attention layers
        override to zero padded steps / mask attention keys."""
        return self.apply(params, x, state, training, rng)

    # -- streaming/truncated-BPTT state (reference: BaseRecurrentLayer
    # stateMap / tBpttStateMap) -----------------------------------------
    def is_rnn(self) -> bool:
        return False

    def init_rnn_state(self, batch: int, dtype=jnp.float32):
        """Zero carry for apply_rnn; None for stateless layers."""
        return None

    def apply_rnn(self, params, x, rnn_state, state, training, rng):
        """Forward one time chunk from an explicit recurrent carry.
        Returns (y, new_rnn_state, new_state)."""
        y, st = self.apply(params, x, state, training, rng)
        return y, rnn_state, st


@dataclass
class DenseLayer(Layer):
    """Reference conf.layers.DenseLayer → layers.feedforward.dense."""

    n_out: int = 0
    has_bias: bool = True

    def set_input_type(self, input_type):
        if isinstance(input_type, FFInput):
            self.n_in = input_type.size
        else:
            raise ValueError(f"DenseLayer needs FF input, got {input_type}")
        return FFInput(self.n_out)

    def init_params(self, key, dtype=jnp.float32):
        kw, _ = jax.random.split(key)
        p = {"W": init_weights(kw, (self.n_in, self.n_out),
                               self.weight_init or "xavier", dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        out = x @ params["W"]
        if self.has_bias:
            out = out + params["b"]
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class ConvolutionLayer(Layer):
    """Reference conf.layers.ConvolutionLayer (2D). W=[out,in,kH,kW]."""

    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Union[Tuple[int, int], str] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"   # truncate | same (reference ConvolutionMode)
    has_bias: bool = True

    def _padding(self):
        return "SAME" if self.convolution_mode.lower() == "same" else self.padding

    def set_input_type(self, input_type):
        if not isinstance(input_type, CNNInput):
            raise ValueError(f"ConvolutionLayer needs CNN input, got {input_type}")
        self.n_in = input_type.channels
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        if self.convolution_mode.lower() == "same":
            oh = -(-input_type.height // sh)
            ow = -(-input_type.width // sw)
        else:
            ph, pw = _pair(self.padding) if not isinstance(self.padding, str) else (0, 0)
            eff_kh, eff_kw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
            oh = (input_type.height + 2 * ph - eff_kh) // sh + 1
            ow = (input_type.width + 2 * pw - eff_kw) // sw + 1
        return CNNInput(self.n_out, oh, ow)

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        p = {"W": init_weights(key, (self.n_out, self.n_in, kh, kw),
                               self.weight_init or "xavier", dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        out = get_op("conv2d").fn(x, params["W"], params.get("b"),
                                  strides=_pair(self.stride), padding=self._padding(),
                                  dilation=_pair(self.dilation))
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class Deconvolution2D(ConvolutionLayer):
    """Reference conf.layers.Deconvolution2D. W=[in,out,kH,kW]."""

    def set_input_type(self, input_type):
        if not isinstance(input_type, CNNInput):
            raise ValueError("Deconvolution2D needs CNN input")
        self.n_in = input_type.channels
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self.convolution_mode.lower() == "same":
            oh, ow = input_type.height * sh, input_type.width * sw
        else:
            ph, pw = _pair(self.padding) if not isinstance(self.padding, str) else (0, 0)
            oh = sh * (input_type.height - 1) + kh - 2 * ph
            ow = sw * (input_type.width - 1) + kw - 2 * pw
        return CNNInput(self.n_out, oh, ow)

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        p = {"W": init_weights(key, (self.n_in, self.n_out, kh, kw),
                               self.weight_init or "xavier", dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        out = get_op("deconv2d").fn(x, params["W"], params.get("b"),
                                    strides=_pair(self.stride), padding=self._padding())
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class DepthwiseConvolution2D(ConvolutionLayer):
    """Reference conf.layers.DepthwiseConvolution2D. W=[mult,C,kH,kW]."""

    depth_multiplier: int = 1

    def set_input_type(self, input_type):
        out_type = ConvolutionLayer.set_input_type(self, input_type)
        return CNNInput(self.n_in * self.depth_multiplier, out_type.height, out_type.width)

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        p = {"W": init_weights(key, (self.depth_multiplier, self.n_in, kh, kw),
                               self.weight_init or "xavier", dtype)}
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_in * self.depth_multiplier,), dtype)
        return p

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        out = get_op("depthwise_conv2d").fn(x, params["W"], params.get("b"),
                                            strides=_pair(self.stride),
                                            padding=self._padding(),
                                            dilation=_pair(self.dilation))
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class SeparableConvolution2D(ConvolutionLayer):
    """Reference conf.layers.SeparableConvolution2D: depthwise + pointwise."""

    depth_multiplier: int = 1

    def init_params(self, key, dtype=jnp.float32):
        kd, kp = jax.random.split(key)
        kh, kw = _pair(self.kernel_size)
        p = {
            "dW": init_weights(kd, (self.depth_multiplier, self.n_in, kh, kw),
                               self.weight_init or "xavier", dtype),
            "pW": init_weights(kp, (self.n_out, self.n_in * self.depth_multiplier, 1, 1),
                               self.weight_init or "xavier", dtype),
        }
        if self.has_bias:
            p["b"] = jnp.zeros((self.n_out,), dtype)
        return p

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        out = get_op("sconv2d").fn(x, params["dW"], params["pW"], params.get("b"),
                                   strides=_pair(self.stride), padding=self._padding())
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class SubsamplingLayer(Layer):
    """Reference conf.layers.SubsamplingLayer (max/avg/pnorm pooling)."""

    pooling_type: str = "max"
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def set_input_type(self, input_type):
        if not isinstance(input_type, CNNInput):
            raise ValueError("SubsamplingLayer needs CNN input")
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self.convolution_mode.lower() == "same":
            oh = -(-input_type.height // sh)
            ow = -(-input_type.width // sw)
        else:
            ph, pw = _pair(self.padding)
            oh = (input_type.height + 2 * ph - kh) // sh + 1
            ow = (input_type.width + 2 * pw - kw) // sw + 1
        return CNNInput(input_type.channels, oh, ow)

    def apply(self, params, x, state, training, rng):
        pad = "SAME" if self.convolution_mode.lower() == "same" else _pair(self.padding)
        kind = self.pooling_type.lower()
        if kind == "max":
            out = get_op("maxpool2d").fn(x, _pair(self.kernel_size), _pair(self.stride), pad)
        elif kind in ("avg", "average"):
            out = get_op("avgpool2d").fn(x, _pair(self.kernel_size), _pair(self.stride), pad)
        elif kind == "pnorm":
            out = get_op("pnormpool2d").fn(x, _pair(self.kernel_size), _pair(self.stride),
                                           pad, pnorm=self.pnorm)
        else:
            raise ValueError(f"unknown pooling type {self.pooling_type!r}")
        return out, state

    @property
    def has_params(self):
        return False


@dataclass
class BatchNormalization(Layer):
    """Reference conf.layers.BatchNormalization: per-channel normalization with
    running-mean/var state (decay), trainable gamma/beta."""

    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    # Fused inference epilogue (ops/pallas_epilogue): collapse inference
    # BN + relu/identity activation into one kernel. None → inherit
    # GlobalConf.fused_epilogue (cascaded by apply_layer_defaults).
    # Opt-in because the folded affine is a reassociation of the dense
    # ops (tolerance-bounded, not bitwise); shape-gated with a dense
    # fallback. Training mode is never fused (batch stats + hand VJP).
    fused_epilogue: Optional[bool] = None

    def set_input_type(self, input_type):
        if isinstance(input_type, CNNInput):
            self.n_in = input_type.channels
        elif isinstance(input_type, FFInput):
            self.n_in = input_type.size
        else:
            raise ValueError("BatchNormalization needs FF or CNN input")
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.ones((self.n_in,), dtype),
                "beta": jnp.zeros((self.n_in,), dtype)}

    def init_state(self):
        return {"mean": jnp.zeros((self.n_in,), jnp.float32),
                "var": jnp.ones((self.n_in,), jnp.float32)}

    def apply(self, params, x, state, training, rng):
        gamma = params.get("gamma")
        beta = params.get("beta")
        axis = 1 if x.ndim == 4 else -1
        if training:
            # fused training form: single-pass statistics + hand VJP (the
            # autodiff of the naive form costs extra full passes over the
            # activations — measured ~10% of a ResNet-50 step on v5e)
            out, mean, var = get_op("batchnorm_train").fn(
                x, gamma, beta, epsilon=self.eps, axis=axis,
                pivot=state["mean"])
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
            if self.fused_epilogue:
                from ...ops.pallas_epilogue import bn_act

                fused = bn_act(x, mean, var, gamma, beta, epsilon=self.eps,
                               axis=axis, act=self.activation)
                if fused is not None:
                    return fused, new_state
            out = get_op("batchnorm").fn(x, mean.astype(x.dtype),
                                         var.astype(x.dtype),
                                         gamma, beta, epsilon=self.eps, axis=axis)
        return activation_fn(self.activation or "identity")(out), new_state


@dataclass
class LocalResponseNormalization(Layer):
    n: int = 5
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75

    def apply(self, params, x, state, training, rng):
        # DL4J applies alpha directly to the squared-window sum (no /n Caffe
        # rescale): out = x / (k + alpha*sum(x^2 over window))^beta
        out = get_op("lrn").fn(x, depth=self.n, bias=self.k,
                               alpha=self.alpha, beta=self.beta)
        return out, state

    @property
    def has_params(self):
        return False


@dataclass
class DropoutLayer(Layer):
    rate: float = 0.5

    def apply(self, params, x, state, training, rng):
        if training and self.rate > 0:
            return get_op("dropout").fn(x, rng, rate=self.rate), state
        return x, state

    @property
    def has_params(self):
        return False


@dataclass
class ActivationLayer(Layer):
    # Optional slope/shape parameter (reference ActivationLReLU/ELU take one);
    # forwarded to ops that accept an alpha (leakyrelu, elu).
    alpha: Optional[float] = None

    def apply(self, params, x, state, training, rng):
        act = (self.activation or "identity").lower()
        if self.alpha is not None and act in ("leakyrelu", "elu"):
            return get_op(act).fn(x, alpha=self.alpha), state
        return activation_fn(act)(x), state

    @property
    def has_params(self):
        return False


@dataclass
class PReLULayer(Layer):
    """Learned leak parameter, per-feature (reference PReLULayer)."""

    def set_input_type(self, input_type):
        if isinstance(input_type, FFInput):
            self.n_in = input_type.size
        elif isinstance(input_type, CNNInput):
            self.n_in = input_type.channels
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        return {"alpha": jnp.zeros((self.n_in,), dtype)}

    def apply(self, params, x, state, training, rng):
        a = params["alpha"]
        if x.ndim == 4:
            a = a.reshape(1, -1, 1, 1)
        return get_op("prelu").fn(x, a), state


@dataclass
class Upsampling2D(Layer):
    size: Tuple[int, int] = (2, 2)

    def set_input_type(self, input_type):
        fh, fw = _pair(self.size)
        return CNNInput(input_type.channels, input_type.height * fh, input_type.width * fw)

    def apply(self, params, x, state, training, rng):
        return get_op("upsampling2d").fn(x, factor=_pair(self.size)), state

    @property
    def has_params(self):
        return False


@dataclass
class ZeroPaddingLayer(Layer):
    padding: Tuple[int, int, int, int] = (1, 1, 1, 1)  # top,bottom,left,right

    def set_input_type(self, input_type):
        t, b, l, r = self.padding
        return CNNInput(input_type.channels, input_type.height + t + b,
                        input_type.width + l + r)

    def apply(self, params, x, state, training, rng):
        t, b, l, r = self.padding
        return jnp.pad(x, ((0, 0), (0, 0), (t, b), (l, r))), state

    @property
    def has_params(self):
        return False


@dataclass
class Cropping2D(Layer):
    cropping: Tuple[int, int, int, int] = (0, 0, 0, 0)

    def set_input_type(self, input_type):
        t, b, l, r = self.cropping
        return CNNInput(input_type.channels, input_type.height - t - b,
                        input_type.width - l - r)

    def apply(self, params, x, state, training, rng):
        t, b, l, r = self.cropping
        h, w = x.shape[2], x.shape[3]
        return x[:, :, t:h - b, l:w - r], state

    @property
    def has_params(self):
        return False


@dataclass
class GlobalPoolingLayer(Layer):
    """Reference conf.layers.GlobalPoolingLayer: pools CNN spatial dims or RNN
    time dim (mask-aware) down to FF."""

    pooling_type: str = "max"

    def set_input_type(self, input_type):
        if isinstance(input_type, CNNInput):
            self._mode = "cnn"
            return FFInput(input_type.channels)
        if isinstance(input_type, RNNInput):
            self._mode = "rnn"
            return FFInput(input_type.size)
        from .inputs import CNN3DInput
        if isinstance(input_type, CNN3DInput):
            self._mode = "cnn3d"
            return FFInput(input_type.channels)
        raise ValueError("GlobalPoolingLayer needs CNN/CNN3D/RNN input")

    def apply(self, params, x, state, training, rng, mask=None):
        kind = self.pooling_type.lower()
        if x.ndim == 5:    # NCDHW
            axes = (2, 3, 4)
        elif x.ndim == 4:
            axes = (2, 3)
        else:  # [B, T, F]
            axes = (1,)
        if kind == "max":
            out = jnp.max(x, axis=axes)
        elif kind in ("avg", "average"):
            if mask is not None and x.ndim == 3:
                m = mask[..., None]
                out = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1e-9)
            else:
                out = jnp.mean(x, axis=axes)
        elif kind == "sum":
            out = jnp.sum(x, axis=axes)
        elif kind == "pnorm":
            out = jnp.sum(jnp.abs(x) ** 2, axis=axes) ** 0.5
        else:
            raise ValueError(f"unknown pooling {self.pooling_type!r}")
        return out, state

    def apply_masked(self, params, x, state, training, rng, fmask):
        """Mask-aware time pooling (reference: masked GlobalPoolingLayer):
        padded steps are excluded from max/avg/sum."""
        if x.ndim != 3:
            return self.apply(params, x, state, training, rng)
        kind = self.pooling_type.lower()
        m = fmask[..., None].astype(x.dtype)
        if kind == "max":
            neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
            out = jnp.max(jnp.where(m > 0, x, neg), axis=1)
        elif kind in ("avg", "average"):
            out = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1e-9)
        elif kind == "sum":
            out = jnp.sum(x * m, axis=1)
        elif kind == "pnorm":
            out = jnp.sum(jnp.abs(x * m) ** 2, axis=1) ** 0.5
        else:
            raise ValueError(f"unknown pooling {self.pooling_type!r}")
        return out, state

    @property
    def has_params(self):
        return False


# --- recurrent ---------------------------------------------------------------


@dataclass
class LSTM(Layer):
    """Reference conf.layers.LSTM (fused impl ≈ LSTMHelpers). Weight layout is
    the fused [nIn+nOut, 4*nOut] IFOG gemm (documented divergence from the
    reference's separate W/RW matrices — same math, one MXU matmul)."""

    n_out: int = 0

    def set_input_type(self, input_type):
        if not isinstance(input_type, RNNInput):
            raise ValueError("LSTM needs RNN input [B, T, F]")
        self.n_in = input_type.size
        return RNNInput(self.n_out, input_type.timesteps)

    def init_params(self, key, dtype=jnp.float32):
        w = init_weights(key, (self.n_in + self.n_out, 4 * self.n_out),
                         self.weight_init or "xavier", dtype)
        b = jnp.zeros((4 * self.n_out,), dtype)
        # forget-gate bias = 1 (reference forgetGateBiasInit default)
        b = b.at[self.n_out:2 * self.n_out].set(1.0)
        return {"W": w, "b": b}

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        ys, _ = get_op("lstm_layer").fn(x, params["W"], params["b"])
        act = self.activation
        if act and act.lower() not in ("tanh", "identity"):
            ys = activation_fn(act)(ys)
        return ys, state

    def apply_masked(self, params, x, state, training, rng, fmask):
        y, st = self.apply(params, x, state, training, rng)
        return y * fmask[:, :, None].astype(y.dtype), st

    def is_rnn(self):
        return True

    def init_rnn_state(self, batch, dtype=jnp.float32):
        z = jnp.zeros((batch, self.n_out), dtype)
        return (z, z)

    def apply_rnn(self, params, x, rnn_state, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        h0, c0 = rnn_state
        ys, (h, c) = get_op("lstm_layer").fn(x, params["W"], params["b"],
                                             h0=h0, c0=c0)
        act = self.activation
        if act and act.lower() not in ("tanh", "identity"):
            ys = activation_fn(act)(ys)
        return ys, (h, c), state


@dataclass
class GravesLSTM(LSTM):
    """Reference GravesLSTM (peepholes omitted — deprecated upstream; the
    non-peephole path is identical to LSTM)."""


@dataclass
class GRU(Layer):
    """GRU layer. ``reset_after=False`` is the reference gruCell form
    (reset applied before the recurrent matmul — libnd4j
    ``generic/recurrent/gruCell.cpp`` semantics); ``reset_after=True`` is
    the CuDNN/Keras form, provided so Keras h5 checkpoints import exactly
    (imports/keras_import.py)."""

    n_out: int = 0
    reset_after: bool = False

    def set_input_type(self, input_type):
        if not isinstance(input_type, RNNInput):
            raise ValueError("GRU needs RNN input [B, T, F]")
        self.n_in = input_type.size
        return RNNInput(self.n_out, input_type.timesteps)

    def init_params(self, key, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        wi = self.weight_init or "xavier"
        p = {"W_ru": init_weights(k1, (self.n_in + self.n_out,
                                       2 * self.n_out), wi, dtype),
             "b_ru": jnp.zeros((2 * self.n_out,), dtype)}
        if self.reset_after:
            p["W_cx"] = init_weights(k2, (self.n_in, self.n_out), wi, dtype)
            p["W_ch"] = init_weights(k3, (self.n_out, self.n_out), wi,
                                     dtype)
            p["b_cx"] = jnp.zeros((self.n_out,), dtype)
            p["b_ch"] = jnp.zeros((self.n_out,), dtype)
        else:
            p["W_c"] = init_weights(k2, (self.n_in + self.n_out,
                                         self.n_out), wi, dtype)
            p["b_c"] = jnp.zeros((self.n_out,), dtype)
        return p

    def _run(self, params, x, h0=None):
        if self.reset_after:
            return get_op("gru_layer_ra").fn(
                x, params["W_ru"], params["W_cx"], params["W_ch"],
                params["b_ru"], params["b_cx"], params["b_ch"], h0=h0)
        return get_op("gru_layer").fn(x, params["W_ru"], params["W_c"],
                                      params["b_ru"], params["b_c"], h0=h0)

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        ys, _ = self._run(params, x)
        return ys, state

    def apply_masked(self, params, x, state, training, rng, fmask):
        y, st = self.apply(params, x, state, training, rng)
        return y * fmask[:, :, None].astype(y.dtype), st

    def is_rnn(self):
        return True

    def init_rnn_state(self, batch, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def apply_rnn(self, params, x, rnn_state, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        ys, h = self._run(params, x, h0=rnn_state)
        return ys, h, state


@dataclass
class SimpleRnn(Layer):
    n_out: int = 0

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return RNNInput(self.n_out, input_type.timesteps)

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {
            "W": init_weights(k1, (self.n_in, self.n_out), self.weight_init or "xavier", dtype),
            "RW": init_weights(k2, (self.n_out, self.n_out), self.weight_init or "xavier", dtype),
            "b": jnp.zeros((self.n_out,), dtype),
        }

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        ys, _ = get_op("simple_rnn_layer").fn(
            x, params["W"], params["RW"], params["b"],
            activation=activation_fn(self.activation or "tanh"))
        return ys, state

    def apply_masked(self, params, x, state, training, rng, fmask):
        y, st = self.apply(params, x, state, training, rng)
        return y * fmask[:, :, None].astype(y.dtype), st

    def is_rnn(self):
        return True

    def init_rnn_state(self, batch, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def apply_rnn(self, params, x, rnn_state, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        ys, h = get_op("simple_rnn_layer").fn(
            x, params["W"], params["RW"], params["b"], h0=rnn_state,
            activation=activation_fn(self.activation or "tanh"))
        return ys, h, state


@dataclass
class Bidirectional(Layer):
    """Reference recurrent.Bidirectional wrapper: runs the wrapped recurrent
    layer forward + on the time-reversed sequence, merges by mode."""

    layer: Optional[Layer] = None
    mode: str = "concat"     # concat | add | mul | average

    def set_input_type(self, input_type):
        out = self.layer.set_input_type(input_type)
        if self.mode.lower() == "concat":
            return RNNInput(out.size * 2, out.timesteps)
        return out

    def init_params(self, key, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        return {"fwd": self.layer.init_params(kf, dtype),
                "bwd": self.layer.init_params(kb, dtype)}

    def apply(self, params, x, state, training, rng):
        fwd, _ = self.layer.apply(params["fwd"], x, {}, training, rng)
        bwd, _ = self.layer.apply(params["bwd"], jnp.flip(x, axis=1), {}, training, rng)
        bwd = jnp.flip(bwd, axis=1)
        mode = self.mode.lower()
        if mode == "concat":
            out = jnp.concatenate([fwd, bwd], axis=-1)
        elif mode == "add":
            out = fwd + bwd
        elif mode == "mul":
            out = fwd * bwd
        else:
            out = 0.5 * (fwd + bwd)
        return out, state


@dataclass
class SelfAttentionLayer(Layer):
    """Reference conf.layers.SelfAttentionLayer → libnd4j
    multi_head_dot_product_attention with Q=K=V=input.

    ``project_input=True`` learns Wq/Wk/Wv/Wo projections (required when
    n_heads > 1); otherwise raw single-head dot-product attention over the
    input and n_out must equal n_in. Input/output [B, T, F]; a feature mask
    masks attention KEYS, so padded timesteps receive no attention weight.
    """

    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None
    project_input: bool = True

    def set_input_type(self, input_type):
        if not isinstance(input_type, RNNInput):
            raise ValueError("SelfAttentionLayer needs RNN input [B, T, F]")
        self.n_in = input_type.size
        if not self.project_input:
            if self.n_heads != 1:
                raise ValueError("project_input=False requires n_heads=1")
            self.n_out = self.n_in
        return RNNInput(self.n_out, input_type.timesteps)

    def _hs(self) -> int:
        return self.head_size or self.n_out // self.n_heads

    def init_params(self, key, dtype=jnp.float32):
        if not self.project_input:
            return {}
        hs = self._hs()
        ks = jax.random.split(key, 4)
        wi = self.weight_init or "xavier"
        return {
            "Wq": init_weights(ks[0], (self.n_in, self.n_heads * hs), wi, dtype),
            "Wk": init_weights(ks[1], (self.n_in, self.n_heads * hs), wi, dtype),
            "Wv": init_weights(ks[2], (self.n_in, self.n_heads * hs), wi, dtype),
            "Wo": init_weights(ks[3], (self.n_heads * hs, self.n_out), wi, dtype),
        }

    def _attend(self, params, q, kv, fmask):
        if self.project_input:
            return get_op("multi_head_dot_product_attention").fn(
                q, kv, kv, params["Wq"], params["Wk"], params["Wv"],
                params["Wo"], num_heads=self.n_heads, mask=fmask)
        m = fmask[:, None, :] if fmask is not None else None
        return get_op("dot_product_attention").fn(q, kv, kv, mask=m)

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        return self._attend(params, x, x, None), state

    def apply_masked(self, params, x, state, training, rng, fmask):
        x = self._maybe_dropout(x, training, rng)
        y = self._attend(params, x, x, fmask)
        return y * fmask[:, :, None].astype(y.dtype), state

    @property
    def has_params(self):
        return self.project_input


@dataclass
class LearnedSelfAttentionLayer(SelfAttentionLayer):
    """Reference conf.layers.LearnedSelfAttentionLayer: n_queries LEARNED
    query vectors attend over the sequence — output is a fixed-length
    [B, n_queries, n_out] regardless of input length (the attention-pooling
    trick the reference uses ahead of feed-forward heads)."""

    n_queries: int = 1

    def set_input_type(self, input_type):
        if not isinstance(input_type, RNNInput):
            raise ValueError("LearnedSelfAttentionLayer needs RNN input")
        self.n_in = input_type.size
        if not self.project_input:
            if self.n_heads != 1:
                raise ValueError("project_input=False requires n_heads=1")
            self.n_out = self.n_in
        return RNNInput(self.n_out, self.n_queries)

    def init_params(self, key, dtype=jnp.float32):
        kq, key = jax.random.split(key)
        p = super().init_params(key, dtype)
        p["Q"] = init_weights(kq, (self.n_queries, self.n_in),
                              self.weight_init or "xavier", dtype)
        return p

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        q = jnp.broadcast_to(params["Q"][None],
                             (x.shape[0],) + params["Q"].shape)
        return self._attend(params, q, x, None), state

    def apply_masked(self, params, x, state, training, rng, fmask):
        x = self._maybe_dropout(x, training, rng)
        q = jnp.broadcast_to(params["Q"][None],
                             (x.shape[0],) + params["Q"].shape)
        # keys masked; output timesteps are the learned queries (all real)
        return self._attend(params, q, x, fmask), state

    @property
    def has_params(self):
        return True


@dataclass
class RecurrentAttentionLayer(Layer):
    """Reference conf.layers.RecurrentAttentionLayer: per timestep,
    y_t = activation(Wx·x_t + Wr·a_t + b) where a_t is multi-head attention
    queried by the previous output y_{t-1} over the whole input sequence.
    The reference defines this via a SameDiff per-step loop; here the step
    is a ``lax.scan`` whose attention logits against the full sequence are
    one batched matmul per step."""

    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None

    def set_input_type(self, input_type):
        if not isinstance(input_type, RNNInput):
            raise ValueError("RecurrentAttentionLayer needs RNN input")
        self.n_in = input_type.size
        return RNNInput(self.n_out, input_type.timesteps)

    def _hs(self) -> int:
        return self.head_size or self.n_out // self.n_heads

    def init_params(self, key, dtype=jnp.float32):
        hs = self._hs()
        ks = jax.random.split(key, 6)
        wi = self.weight_init or "xavier"
        return {
            "Wx": init_weights(ks[0], (self.n_in, self.n_out), wi, dtype),
            "Wr": init_weights(ks[1], (self.n_out, self.n_out), wi, dtype),
            "b": jnp.zeros((self.n_out,), dtype),
            "Wq": init_weights(ks[2], (self.n_out, self.n_heads * hs), wi, dtype),
            "Wk": init_weights(ks[3], (self.n_in, self.n_heads * hs), wi, dtype),
            "Wv": init_weights(ks[4], (self.n_in, self.n_heads * hs), wi, dtype),
            "Wo": init_weights(ks[5], (self.n_heads * hs, self.n_out), wi, dtype),
        }

    def _run(self, params, x, fmask):
        act = activation_fn(self.activation or "tanh")
        mha = get_op("multi_head_dot_product_attention").fn
        xT = jnp.swapaxes(x, 0, 1)                     # [T, B, F]
        y0 = jnp.zeros((x.shape[0], self.n_out), x.dtype)

        def step(y_prev, xt):
            a = mha(y_prev[:, None, :], x, x, params["Wq"], params["Wk"],
                    params["Wv"], params["Wo"], num_heads=self.n_heads,
                    mask=fmask)[:, 0]
            y = act(xt @ params["Wx"] + a @ params["Wr"] + params["b"])
            return y, y

        _, ys = jax.lax.scan(step, y0, xT)
        return jnp.swapaxes(ys, 0, 1)

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        return self._run(params, x, None), state

    def apply_masked(self, params, x, state, training, rng, fmask):
        x = self._maybe_dropout(x, training, rng)
        y = self._run(params, x, fmask)
        return y * fmask[:, :, None].astype(y.dtype), state


@dataclass
class LastTimeStep(Layer):
    """Reference recurrent.LastTimeStep wrapper: RNN [B,T,F] → FF [B,F]."""

    layer: Optional[Layer] = None

    def set_input_type(self, input_type):
        out = self.layer.set_input_type(input_type)
        return FFInput(out.size)

    def init_params(self, key, dtype=jnp.float32):
        return self.layer.init_params(key, dtype)

    def apply(self, params, x, state, training, rng):
        ys, state = self.layer.apply(params, x, state, training, rng)
        return ys[:, -1], state


# --- embeddings --------------------------------------------------------------


@dataclass
class EmbeddingLayer(Layer):
    """Reference conf.layers.EmbeddingLayer: int index [B] (or one-hot) → [B, nOut].

    ``table_sharding`` names a mesh axis to row-shard the table over
    (SURVEY §2.4 row 4 — the VoidParameterServer translation). When the
    layer runs inside a ``shard_map`` binding that axis (ParallelWrapper
    with ``model_axis``), lookups become masked-local-gather + psum and
    the gradient scatter touches only owned rows; outside any mesh the
    layer behaves exactly like the dense one."""

    n_out: int = 0
    table_sharding: Optional[str] = None

    def set_input_type(self, input_type):
        self.n_in = input_type.size  # vocab size
        return FFInput(self.n_out)

    def init_params(self, key, dtype=jnp.float32):
        return {"W": init_weights(key, (self.n_in, self.n_out),
                                  self.weight_init or "xavier", dtype)}

    def _lookup(self, W, idx):
        if self.table_sharding:
            from ...ops.embeddings import sharded_rows_lookup
            try:
                rows, _ = sharded_rows_lookup(W, idx, self.table_sharding)
                return rows
            except NameError:
                pass   # axis not bound: plain single-table lookup
        return jnp.take(W, idx, axis=0)

    def apply(self, params, x, state, training, rng):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim == 2 and x.shape[-1] == self.n_in:
            idx = jnp.argmax(x, axis=-1)  # one-hot form
        else:
            idx = x.astype(jnp.int32)
            if idx.ndim == 2 and idx.shape[-1] == 1:
                idx = idx[:, 0]
        out = self._lookup(params["W"], idx)
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class EmbeddingSequenceLayer(EmbeddingLayer):
    """[B, T] int → RNN [B, T, nOut]."""

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        ts = getattr(input_type, "timesteps", None)
        return RNNInput(self.n_out, ts)

    def apply(self, params, x, state, training, rng):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        out = self._lookup(params["W"], idx)
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class ElementWiseMultiplicationLayer(Layer):
    """out = activation(w * x + b), elementwise (reference layer of same name)."""

    def set_input_type(self, input_type):
        self.n_in = input_type.size
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        return {"w": jnp.ones((self.n_in,), dtype), "b": jnp.zeros((self.n_in,), dtype)}

    def apply(self, params, x, state, training, rng):
        out = x * params["w"] + params["b"]
        return activation_fn(self.activation or "identity")(out), state


@dataclass
class FrozenLayer(Layer):
    """Reference FrozenLayer wrapper: parameters excluded from updates.
    Implemented with stop_gradient — updater math never sees a gradient."""

    layer: Optional[Layer] = None

    def set_input_type(self, input_type):
        return self.layer.set_input_type(input_type)

    def init_params(self, key, dtype=jnp.float32):
        return self.layer.init_params(key, dtype)

    def init_state(self):
        return self.layer.init_state()

    def apply(self, params, x, state, training, rng):
        frozen = jax.tree.map(jax.lax.stop_gradient, params)
        return self.layer.apply(frozen, x, state, training, rng)

    def apply_masked(self, params, x, state, training, rng, fmask):
        frozen = jax.tree.map(jax.lax.stop_gradient, params)
        return self.layer.apply_masked(frozen, x, state, training, rng, fmask)

    def is_rnn(self):
        return self.layer.is_rnn()

    def init_rnn_state(self, batch, dtype=jnp.float32):
        return self.layer.init_rnn_state(batch, dtype)

    def apply_rnn(self, params, x, rnn_state, state, training, rng):
        frozen = jax.tree.map(jax.lax.stop_gradient, params)
        return self.layer.apply_rnn(frozen, x, rnn_state, state, training, rng)

    @property
    def has_params(self):
        return self.layer.has_params


# --- output layers -----------------------------------------------------------


@dataclass
class OutputLayer(DenseLayer):
    """Reference conf.layers.OutputLayer: dense + loss head."""

    loss: Union[str, ILossFunction, None] = None

    def __post_init__(self):
        if self.loss is None:
            self.loss = LossMCXENT()
        elif isinstance(self.loss, str):
            self.loss = loss_from_name(self.loss)
        if self.activation is None:
            self.activation = "softmax"

    def pre_output(self, params, x):
        out = x @ params["W"]
        if self.has_bias:
            out = out + params["b"]
        return out

    def apply(self, params, x, state, training, rng):
        x = self._maybe_dropout(x, training, rng)
        return activation_fn(self.activation)(self.pre_output(params, x)), state

    def compute_score(self, params, x, labels, mask=None, average: bool = True):
        pre = self.pre_output(params, x)
        return self.loss.compute_score(labels, pre, self.activation, mask, average)


@dataclass
class RnnOutputLayer(OutputLayer):
    """Per-timestep output head on [B, T, F] (reference RnnOutputLayer):
    the dense W=[nIn,nOut] applies at every timestep (matmul broadcasts)."""

    def set_input_type(self, input_type):
        if not isinstance(input_type, RNNInput):
            raise ValueError(f"RnnOutputLayer needs RNN input, got {input_type}")
        self.n_in = input_type.size
        return RNNInput(self.n_out, input_type.timesteps)


@dataclass
class LossLayer(Layer):
    """No-param loss head (reference conf.layers.LossLayer).
    ``loss_weight``: what this head's score counts for in a network's total
    loss (``ComputationGraph`` sums its outputs' scores)."""

    loss: Union[str, ILossFunction, None] = None
    loss_weight: float = 1.0

    def __post_init__(self):
        if self.loss is None:
            self.loss = LossMCXENT()
        elif isinstance(self.loss, str):
            self.loss = loss_from_name(self.loss)
        if self.activation is None:
            self.activation = "identity"

    def pre_output(self, params, x):
        return x

    def apply(self, params, x, state, training, rng):
        return activation_fn(self.activation)(x), state

    def compute_score(self, params, x, labels, mask=None, average: bool = True):
        return self.loss.compute_score(labels, x, self.activation, mask, average)

    @property
    def has_params(self):
        return False


# extended families (1D/3D convs, capsules, VAE, YOLO, constraints, ...)
from .layers_ext import *  # noqa: E402,F401,F403
# sequence-model layers (state-space scan, differential attention, tied head)
from .layers_seq import *  # noqa: E402,F401,F403
