"""KronScope scopes in compiled programs, and the compile counters.

A scope (``telemetry.scope``) names the program layer that issued each
instruction in the compiled HLO's ``op_name`` metadata, whether telemetry is
on or off, and changes nothing else: compiled with every scope replaced by
a null context, a program has the same instructions.  The compile counters
(``compile_cache``) count JAX's compiles and persistent-cache hits.
"""
import contextlib
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import engine
from repro.models.config import reduced
from repro.optim import OptConfig
from repro.runtime import compile_cache, telemetry
from repro.runtime.hlo_analysis import strip_source_locations
from repro.train import make_train_step, train_state_init

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
_METADATA = re.compile(r",? metadata=\{[^}]*\}")


@pytest.fixture(autouse=True)
def _fresh_state():
    telemetry.reset()
    yield
    telemetry.reset()


def _kron_args():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 16), jnp.float32)
    fs = tuple(jnp.asarray(rng.randn(4, 4), jnp.float32) for _ in range(2))
    return x, fs


def _kron_fwd():
    op = engine.KronOp((4, 4), (4, 4))
    return (lambda x, fs: op(x, fs)), _kron_args()


def _kron_grad():
    op = engine.KronOp((4, 4), (4, 4))
    ct = jnp.asarray(np.random.RandomState(1).randn(16, 16), jnp.float32)

    def loss(x, fs):
        return jnp.vdot(op(x, fs), ct)

    return jax.grad(loss, argnums=(0, 1)), _kron_args()


def _train_step():
    cfg = reduced(get_config("qwen3_4b"), n_layers=2, d_model=32, n_heads=2,
                  n_kv_heads=2, head_dim=16, d_ff=64, vocab=64,
                  vocab_pad_multiple=32, dtype="float32")
    cfg = dataclasses.replace(cfg, kron_ffn=True)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=1, decay_steps=10)
    state = train_state_init(cfg, opt_cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    return make_train_step(cfg, opt_cfg), (state, {"tokens": toks, "labels": toks})


PROGRAMS = {"kron_fwd": _kron_fwd, "kron_grad": _kron_grad, "train_step": _train_step}


def _compiled_text(program: str) -> str:
    fn, args = PROGRAMS[program]()
    return jax.jit(fn).lower(*args).compile().as_text()


def _instructions(hlo_text: str) -> str:
    """The compiled text without metadata or source locations."""
    return _METADATA.sub("", strip_source_locations(hlo_text))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_leave_the_instructions_unchanged(program, monkeypatch):
    scoped = _compiled_text(program)
    assert "kronscope." in scoped
    monkeypatch.setattr(telemetry, "scope", lambda name: contextlib.nullcontext())
    bare = _compiled_text(program)
    assert "kronscope." not in bare
    assert _instructions(bare) == _instructions(scoped)


@pytest.mark.parametrize("scope, program", [
    ("stage", "kron_fwd"), ("program", "kron_fwd"), ("op", "kron_fwd"),
    ("stage_grad", "kron_grad"), ("op_bwd", "kron_grad"),
    ("model.attn", "train_step"), ("model.ffn", "train_step"),
    ("model.head", "train_step"), ("optim.update", "train_step"),
])
def test_scope_is_in_the_compiled_program_with_telemetry_off(scope, program):
    assert not telemetry.active()
    names = re.findall(r'op_name="([^"]*)"', _compiled_text(program))
    assert any(re.search(rf"kronscope\.{re.escape(scope)}(?:[/)]|$)", n) for n in names)


@pytest.mark.parametrize("path", ["kernels", "core/distributed.py", "optim"])
def test_no_span_inside_jitted_code(path):
    """Spans time the host; in code that runs under jit tracing they would
    time the trace.  Those regions are scopes."""
    files = [SRC / path] if path.endswith(".py") else sorted((SRC / path).glob("*.py"))
    assert files
    for f in files:
        assert "telemetry.span(" not in f.read_text(), f


@pytest.fixture()
def fresh_cache(tmp_path):
    """A persistent compile cache of its own for one test, restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_compile_counters_count_a_compile_then_a_cache_hit(fresh_cache):
    compile_cache.listen()
    x = np.arange(12.0, dtype=np.float32).reshape(3, 4)

    def f(x):
        return jnp.sin(x) @ x.T + 3.0

    telemetry.configure()
    before = compile_cache.totals()
    jax.jit(f).lower(x).compile()
    first = compile_cache.totals()
    jax.clear_caches()
    jax.jit(f).lower(x).compile()
    second = compile_cache.totals()
    counters = telemetry.snapshot()["counters"]

    def delta(a, b, k):
        return b.get(k, 0) - a.get(k, 0)

    assert delta(before, first, "compile.cache_misses") == 1
    assert delta(before, first, "compile.cache_hits") == 0
    assert delta(before, first, "compile.trace_s") > 0
    assert delta(before, first, "compile.backend_s") > 0
    assert delta(first, second, "compile.cache_hits") == 1
    assert delta(first, second, "compile.cache_misses") == 0
    assert delta(first, second, "compile.cache_load_s") > 0
    # telemetry's counters are the same counts, from when it was configured
    for k in ("compile.cache_hits", "compile.cache_misses"):
        assert counters.get(k, 0) == delta(before, second, k)


def test_compile_counters_count_a_nested_trace_once():
    compile_cache.listen()
    inner = jax.jit(lambda x: jnp.cos(x) * 2.0)

    def outer(x):
        return inner(x) + inner(x * 3.0)

    before = compile_cache.totals().get("compile.trace_s", 0)
    compile_cache._on_start(compile_cache.TRACE_EVENTS[0], 0.0)
    compile_cache._on_start(compile_cache.TRACE_EVENTS[0], 0.0)
    compile_cache._on_duration(compile_cache.TRACE_EVENTS[0], 1.0)  # nested
    compile_cache._on_duration(compile_cache.BACKEND_EVENT, 0.5)  # inside the trace
    compile_cache._on_duration(compile_cache.TRACE_EVENTS[0], 3.0)  # outermost
    after = compile_cache.totals().get("compile.trace_s", 0)
    assert after - before == pytest.approx(2.5)
    jax.jit(outer).lower(np.ones(3, np.float32))  # real nesting: no error, depth back at 0
    assert compile_cache._state()["depth"] == 0
