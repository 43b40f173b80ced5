"""Persistent compilation cache (SURVEY §5.6; VERDICT r3 weak #7).

The reference ships prebuilt libnd4j binaries, so a fresh JVM never pays
kernel compilation; the XLA analog is jax's persistent executable cache.
These tests pin the library-level knob: ``Environment.set_compile_cache``
must make a SECOND process reuse the first process's executables instead of
recompiling, and the one placement rule: ``JAX_COMPILATION_CACHE_DIR`` where
set (no directory set in code), else ``<checkout>/.jax_cache``.

Cache hits are asserted structurally (no new cache entries are written by
the second process) rather than by wall-clock, which would be flaky on a
loaded CI host.
"""

import os
import subprocess
import sys
import tempfile

import pytest

_FIT_SCRIPT = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
from deeplearning4j_tpu.common.environment import Environment
Environment.get().set_compile_cache({cache!r}, min_compile_secs=0.0)

import numpy as np
from deeplearning4j_tpu.nlp import Word2Vec

rng = np.random.default_rng(0)
words = np.array([f"w{{i}}" for i in range(200)])
ids = rng.integers(0, 200, size=(300, 12))
sents = [" ".join(r) for r in words[ids]]
t0 = time.perf_counter()
w = Word2Vec(min_word_frequency=1, layer_size=16, negative=3, epochs=1,
             batch_size=128, seed=7)
w.set_sentence_iterator(sents)
w.fit()
print("FIT_SECONDS", time.perf_counter() - t0)
assert np.isfinite(w.last_loss)
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_fit(cache_dir: str) -> float:
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    out = subprocess.run(
        [sys.executable, "-c",
         _FIT_SCRIPT.format(repo=_REPO, cache=cache_dir)],
        capture_output=True, text=True, env=env, cwd=_REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("FIT_SECONDS"):
            return float(line.split()[1])
    raise AssertionError(f"no FIT_SECONDS in output: {out.stdout!r}")


def _cache_entries(cache_dir: str):
    return sorted(
        os.path.join(dp, f)
        for dp, _, fs in os.walk(cache_dir) for f in fs)


def _run_script(body: str, cache_env, cwd: str = _REPO) -> None:
    """Run ``body`` in a fresh process with JAX_COMPILATION_CACHE_DIR set to
    ``cache_env`` (None: unset)."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r)\n%s" % (_REPO, body)],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


class TestCompileCache:
    @pytest.mark.slow
    def test_second_process_hits_cache(self):
        with tempfile.TemporaryDirectory() as cache:
            _run_fit(cache)
            entries = _cache_entries(cache)
            assert entries, "first process wrote no cache entries"
            _run_fit(cache)
            assert _cache_entries(cache) == entries, \
                "second process recompiled (new cache entries) instead " \
                "of loading the persisted executables"

    def test_env_var_knob(self):
        # JAX_COMPILATION_CACHE_DIR places the cache with no
        # set_compile_cache call at all: jax reads it, Environment reports it
        with tempfile.TemporaryDirectory() as cache:
            _run_script(
                "from deeplearning4j_tpu.common.environment import "
                "Environment\n"
                "e = Environment.get()\n"
                "assert e.compile_cache_dir() == %r, e.compile_cache_dir()\n"
                "import jax\n"
                "assert jax.config.jax_compilation_cache_dir == %r\n"
                % (cache, cache), cache_env=cache)

    def test_env_var_set_no_directory_in_code(self):
        # the one placement rule, first half: where the variable is set the
        # library sets NO directory in code (it only reports jax's own)
        with tempfile.TemporaryDirectory() as cache:
            _run_script(
                "import jax\n"
                "updates = []\n"
                "real = jax.config.update\n"
                "def spy(name, value):\n"
                "    updates.append(name)\n"
                "    real(name, value)\n"
                "jax.config.update = spy\n"
                "from deeplearning4j_tpu.common.environment import (\n"
                "    Environment, enable_compilation_cache)\n"
                "assert enable_compilation_cache() == %r\n"
                "assert Environment.get().set_compile_cache() == %r\n"
                "assert 'jax_compilation_cache_dir' not in updates, updates\n"
                "assert jax.config.jax_compilation_cache_dir == %r\n"
                % (cache, cache, cache), cache_env=cache)

    def test_default_dir_is_the_checkout_from_any_cwd(self):
        # second half: without the variable the cache is
        # <checkout>/.jax_cache whatever the working directory (the path
        # is part of jax's cache key — a cache that moves never hits)
        want = os.path.join(_REPO, ".jax_cache")
        script = (
            "from deeplearning4j_tpu.common.environment import "
            "enable_compilation_cache\n"
            "import jax, os\n"
            "assert enable_compilation_cache() == %r\n"
            "assert jax.config.jax_compilation_cache_dir == %r\n"
            "assert not os.path.exists(os.path.join(os.getcwd(), "
            "'.jax_cache'))\n" % (want, want))
        with tempfile.TemporaryDirectory() as a, \
                tempfile.TemporaryDirectory() as b:
            for cwd in (a, b):
                _run_script(script, cache_env=None, cwd=cwd)


_MLN_FIT_SCRIPT = r"""
import os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
from deeplearning4j_tpu.common.environment import Environment
Environment.get().set_compile_cache({cache!r}, min_compile_secs=0.0)

import numpy as np
from deeplearning4j_tpu.data import DataSet
from deeplearning4j_tpu.learning import Nesterovs
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L

conf = (NeuralNetConfiguration.builder().seed(123)
        .updater(Nesterovs(learning_rate=0.01, momentum=0.9))
        .activation("relu").weight_init("xavier").list()
        .layer(L.ConvolutionLayer(n_out=8, kernel_size=(5, 5)))
        .layer(L.SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
        .layer(L.DenseLayer(n_out=32))
        .layer(L.OutputLayer(n_out=10, loss="mcxent", activation="softmax"))
        .set_input_type(InputType.convolutional(28, 28, 1)).build())
model = MultiLayerNetwork(conf).init()
rng = np.random.RandomState(0)
x = rng.randn(32, 1, 28, 28).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 32)]
model.fit(DataSet(x, y))
print("FIT_SECONDS", 0.0)
assert np.isfinite(float(model._score_dev))
"""


def _run_mln_fit(cache_dir: str) -> None:
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    out = subprocess.run(
        [sys.executable, "-c",
         _MLN_FIT_SCRIPT.format(repo=_REPO, cache=cache_dir)],
        capture_output=True, text=True, env=env, cwd=_REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]


class TestMLNColdStart:
    """Round-5 item 6: the cache path must serve the MultiLayerNetwork
    train step too (the bench --cold-audit flagship path), asserted
    structurally like TestCompileCache."""

    @pytest.mark.slow
    def test_mln_second_process_hits_cache(self):
        with tempfile.TemporaryDirectory() as cache:
            _run_mln_fit(cache)
            entries = _cache_entries(cache)
            assert entries, "first MLN process wrote no cache entries"
            _run_mln_fit(cache)
            assert _cache_entries(cache) == entries, \
                "second MLN process recompiled instead of loading the " \
                "persisted executables"
