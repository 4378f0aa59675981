"""The system under test, stood up for one cell: the core LLM engine and
the retrieval engines built through their own constructors with the
benchmark's weights, the app and the Teola orchestrator over them.

The served path is the one the launcher's ``--paged-kv
--continuous-batching --chunked-prefill --prefix-cache radix`` builds:
``Teola`` -> ``GraphScheduler`` -> engine schedulers -> ``LLMEngine``
with the paged KV pool, the continuous decode loop, chunked prefill and
the radix prefix cache -> ``models/transformer.py``.

Weights are made by the configuration's reference module from the seed
(on the device, in the served dtype, one jitted call) and handed to the
engine in place of its own initialisation, so that the reference and
the system run the same weights without the reference taking anything
the system made.
"""
from __future__ import annotations

import contextlib
import gc
from typing import Optional

import jax
import jax.numpy as jnp

from harness import device
from harness.spec import BENCH_DIR, load_module

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
LLM_JITS = ("_step", "_pstep", "_paged_step", "_paged_pstep")


def program_model_config(name: str, arch, cfg: dict):
    """The system's model configuration for configuration ``cfg``."""
    from repro.configs.base import LayerSpec, ModelConfig, Stage
    kw = arch.program_config(cfg)
    layers = kw.pop("layers")
    return ModelConfig(name=name, family="dense",
                       stages=(Stage(pattern=(LayerSpec(kind="attn"),),
                                     repeat=layers),), **kw)


@contextlib.contextmanager
def _weights_from(params):
    """While constructing an engine, its ``init_params`` returns
    ``params`` (the engine then commits them to its device)."""
    from repro.engines import llm_engine
    orig = llm_engine.init_params
    llm_engine.init_params = lambda *a, **k: params
    try:
        yield
    finally:
        llm_engine.init_params = orig


def pool_blocks(serving: dict, block_bytes: int) -> Optional[int]:
    """Blocks of the paged KV pool: the device memory left after the
    weights, less ``pool_margin_bytes`` for the steps' temporaries, over
    two, since the served step writes a second copy of the pool, then
    rounded down to a multiple of 64 so that every run builds the same
    shapes. None where the backend reports no memory (the CPU), which
    leaves the engine's own default."""
    mem = device.memory()
    if "bytes_limit" not in mem:
        return None
    free = mem["bytes_limit"] - mem["bytes_in_use"] \
        - int(serving["pool_margin_bytes"])
    return int(free // (2 * block_bytes)) // 64 * 64


class Stack:
    """Weights, engines and orchestrator of one cell."""

    def __init__(self, cell, seed: int, log=print):
        self.cell = cell
        cfg = cell.config
        self.serving = cfg["serving"]
        self.arch = cell.arch
        self.enc_mod = load_module(
            BENCH_DIR / "configs" / f"{cfg['encoders']['architecture']}.py",
            "bench_arch_" + cfg["encoders"]["architecture"])
        self.mcfg = program_model_config(cell.config_name, self.arch, cfg)
        self.weights = None
        self.enc_weights = None
        self.make_weights(seed)
        self.llm = None
        self.engines = None
        self.orch = None
        self.log = log
        self._jits = None       # the first build's programs, reused
        self._blocks = None     # and its pool size

    def make_weights(self, seed: int):
        """(Re)make every weight from ``seed``; the old ones are dropped
        first, so that two copies never share the device."""
        self.weights = self.enc_weights = None
        gc.collect()
        cfg = self.cell.config
        dtype = DTYPES[self.serving["dtype"]]
        self.weights = self.arch.make_weights(cfg, seed, dtype)
        enc = cfg["encoders"]
        self.enc_weights = {
            "embedder": self.enc_mod.make_weights(enc["embedder"],
                                                  seed + 1),
            "reranker": self.enc_mod.make_weights(enc["reranker"],
                                                  seed + 2)}
        jax.block_until_ready((self.weights, self.enc_weights))

    def build(self):
        """Engines, app and orchestrator on the current weights."""
        from repro.core.apps import ALL_APPS
        from repro.core.teola import Teola
        from repro.engines.encoder_engines import (EmbeddingEngine,
                                                   RerankEngine)
        from repro.engines.llm_engine import LLMEngine
        from repro.engines.model_free import ChunkerEngine, VectorDBEngine
        from repro.serving import kv_cache as kvc
        s = self.serving
        bs = int(s["block_size"])
        if self._jits is None:
            self._blocks = pool_blocks(s, kvc.paged_block_bytes(self.mcfg,
                                                                bs))
        blocks = self._blocks
        with _weights_from(self.weights):
            self.llm = LLMEngine(
                "core_llm", self.mcfg,
                max_len=self.arch.dims(self.cell.config)["max_len"],
                max_batch=int(s["max_batch"]), dtype=DTYPES[s["dtype"]],
                paged=True, block_size=bs, num_blocks=blocks,
                chunked_prefill=True, prefill_chunk=int(s["prefill_chunk"]),
                prefix_cache="radix")
        emb = EmbeddingEngine(max_batch=int(s["encoder_max_batch"]))
        rer = RerankEngine(max_batch=int(s["encoder_max_batch"]))
        if self._jits is None:
            self._jits = {"llm": {k: getattr(self.llm, k) for k in LLM_JITS},
                          "emb": emb._fwd, "rer": rer._fwd}
        else:
            # a rebuild (fresh pool, queues and caches) runs the programs
            # the first build traced and compiled
            for k, f in self._jits["llm"].items():
                setattr(self.llm, k, f)
            emb._fwd, rer._fwd = self._jits["emb"], self._jits["rer"]
        for eng, key in ((emb, "embedder"), (rer, "reranker")):
            want = jax.tree.map(lambda a: (a.shape, a.dtype), eng.params)
            got = jax.tree.map(lambda a: (a.shape, a.dtype),
                               self.enc_weights[key])
            if want != got:
                raise ValueError(f"{key}: the configuration's encoder "
                                 f"sizes do not match the system's")
            eng.params = self.enc_weights[key]
        self.engines = {"core_llm": self.llm, "embedding": emb,
                        "rerank": rer, "vectordb": VectorDBEngine(),
                        "chunker": ChunkerEngine()}
        app = ALL_APPS[self.cell.traffic["app"]](self.engines)
        self.orch = Teola(app, self.engines, policy="topo",
                          continuous_batching=True)
        self.log(f"engines: core_llm {self.cell.config_name} "
                 f"{self.mcfg.num_layers} layers, pool {self.llm.num_blocks}"
                 f" blocks of {bs} tokens, max_batch {self.llm.max_batch}")

    def teardown(self):
        """Stop the orchestrator and drop the engines (and the KV pool);
        the weights stay for the reference."""
        if self.orch is not None:
            self.orch.shutdown()
        self.orch = self.engines = self.llm = None
        gc.collect()
