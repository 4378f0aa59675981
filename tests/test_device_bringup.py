"""CPU checks of the device bring-up rules: jitted weight init, Pallas
interpret mode, the compile-cache location, failing exit statuses of the
launcher and of ``chip_smoke.py``, replica placement across devices, and
the serving paths' logits against the float32 reference."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import resolve_interpret
from repro.kernels.decode_attention import verify_attention
from repro.launch import device
from repro.models.transformer import init_params

ROOT = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def _run(args, env=None, timeout=600, cwd=ROOT):
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
           **(env or {})}
    return subprocess.run([sys.executable] + args, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- weight init ------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_init_params_under_jit_matches_eager_build(dtype):
    """The jitted init gives an eager build's values for the same seed,
    up to the one rounding the compiler moves (a draw's scale folded
    into normal()'s sqrt(2)): at most one ulp in the target dtype, and
    nearly every element bit-identical."""
    cfg = get_config("chatglm3-6b").reduced()
    jitted = init_params(cfg, jax.random.key(3), dtype)
    with jax.disable_jit():
        eager = init_params(cfg, jax.random.key(3), dtype)
    assert jax.tree.structure(jitted) == jax.tree.structure(eager)
    uint = {jnp.float32: np.uint32, jnp.bfloat16: np.uint16}[dtype]
    same = total = 0
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        ia = np.asarray(a).view(uint).astype(np.int64)
        ib = np.asarray(b).view(uint).astype(np.int64)
        assert np.abs(ia - ib).max() <= 1       # same sign: ulp distance
        same += int((ia == ib).sum())
        total += ia.size
    assert same / total > 0.6


# -- Pallas interpret mode --------------------------------------------------

def test_interpret_mode_follows_the_backend(monkeypatch):
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True)


def test_raw_kernel_refuses_interpret_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 1, 4, 64), jnp.float32)
    pool = jnp.zeros((3, 16, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        verify_attention(q, pool, pool, jnp.ones((1, 2), jnp.int32),
                         jnp.ones((1,), jnp.int32), interpret=True)


# -- compile cache ----------------------------------------------------------

def test_compile_cache_defaults_to_fixed_path_in_checkout(monkeypatch):
    assert str(device.DEFAULT_CACHE_DIR) == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    assert device.compile_cache_dir() == str(device.DEFAULT_CACHE_DIR)
    device.startup("test")
    assert updates == [("jax_compilation_cache_dir",
                        str(device.DEFAULT_CACHE_DIR))]
    updates.clear()
    monkeypatch.setenv(device.CACHE_ENV, "/elsewhere/cache")
    assert device.compile_cache_dir() == "/elsewhere/cache"
    device.startup("test")
    assert updates == []          # JAX reads the variable itself


def test_compile_cache_entries_land_where_the_env_says(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.device import startup\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "startup('t')\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()\n")
    cache = tmp_path / "cache"
    r = _run(["-c", code], env={device.CACHE_ENV: str(cache)})
    assert r.returncode == 0, r.stderr[-2000:]
    assert str(cache) in r.stdout
    assert any(cache.iterdir())


# -- exit statuses ----------------------------------------------------------

def test_serve_exits_nonzero_when_a_query_fails():
    r = _run(["-m", "repro.launch.serve", "--sim", "--app", "naive_rag",
              "--queries", "2", "--continuous-batching",
              "--fault-inject", "crash:core_llm:decode:1",
              "--max-retries", "0", "--request-deadline", "20"],
             env={"REPRO_SIM_SPEED": "16"}, timeout=300)
    assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
    assert "failed: ReplicaCrash" in r.stdout


def test_chip_smoke_fails_without_a_tpu():
    r = _run([os.path.join(ROOT, "chip_smoke.py")], timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "platform=cpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_fails_alone_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path,
             env={"PYTHONPATH": ""}, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


# -- replicas one per device ------------------------------------------------

REPLICAS = r"""
import jax, numpy as np
from repro.configs.base import get_config
from repro.core.engine_pool import EnginePool
from repro.engines.decode_loop import DecodeSeq
from repro.engines.llm_engine import LLMEngine

def on(tree):
    return {d for a in jax.tree.leaves(tree) for d in a.devices()}

def prefill(eng, sid, toks):
    st = eng.new_state()
    eng.states[sid] = st
    eng.prefill_batch([(st, toks)])

def decode(eng, sid, n=6):
    seq = DecodeSeq(sid, eng.states[sid], n, text_fn=eng.tok.decode)
    for _ in range(n):
        eng.decode_iteration([seq])
    eng.release(sid)
    return list(seq.tokens)

cfg = get_config("tiny-core-llm")
for paged in (True, False):
    pool = EnginePool.replicate(LLMEngine("core_llm", cfg, paged=paged), 4)
    devs = [r.device for r in pool]
    assert devs == jax.devices()[:4], devs
    for r in pool:
        assert on(r.params) == {r.device}
        if paged:
            assert on(r.pool) == {r.device}
    toks = pool[0].tok.encode("what is fact 3 about optics and lenses")
    outs = []
    for r in pool:
        prefill(r, "s", toks)
        outs.append(decode(r, "s"))
    assert all(o == outs[0] for o in outs), outs
    # prefill on replica 0, move the sequence to replica 2, decode there
    prefill(pool[0], "m", toks)
    pool[2].import_seq(pool[0].export_seq("m"))
    assert "m" not in pool[0].states
    if paged:
        assert on(pool[2].pool) == {pool[2].device}
    else:
        assert on(pool[2].states["m"].cache) == {pool[2].device}
    assert decode(pool[2], "m") == outs[0]
    print("OK", "paged" if paged else "dense", devs)
"""


def test_four_replicas_sit_on_four_devices_and_migrate():
    r = _run(["-c", REPLICAS], timeout=600, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    assert "OK paged" in r.stdout and "OK dense" in r.stdout


# -- logits against the float32 reference ------------------------------------

def test_paged_bf16_logits_match_reference_within_smoke_tolerance():
    """chip_smoke's logits phase at ChatGLM3's layer layout (QKV bias,
    half-dim RoPE, GQA) cut to test size: bf16 paged prefill and decode
    logits against the cache-free float32 reference."""
    from repro.engines.llm_engine import LLMEngine
    cs = _chip_smoke()
    eng = LLMEngine("core_llm", get_config("chatglm3-6b").reduced(),
                    dtype=jnp.bfloat16, paged=True, max_batch=2)
    ok, rel_rms, max_rel = cs.logits_check(eng)
    assert ok, (rel_rms, max_rel)
    assert rel_rms > 0                  # bf16 really ran
    assert eng.alloc.free_blocks() == eng.alloc.capacity


def test_smoke_serving_phases_run_at_tiny_size():
    """chip_smoke's serving phases through the launcher's own code, on
    the CPU at the tiny size: every query completes."""
    cs = _chip_smoke()
    counter = cs.CompileCounter()
    for flags in ([], cs.PAGED_FLAGS):
        args = cs.serve_args("tiny-core-llm", "bfloat16", flags, queries=2)
        served = cs.build_phase("test", args)
        answers, failures = cs.serve_phase("test", served, args, counter)
        cs.teardown(served)
        assert not failures
        assert all(answers)
    assert counter.compiles > 0
