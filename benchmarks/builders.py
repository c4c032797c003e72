"""Builds the system under test from a configuration file.

The default builder is the ``LlamaConfig`` path: the constructors
``chip_smoke.py`` uses (``llama.init_params`` on the device from the seed,
``Generator``, ``ServeEngine``), at the sizes the file states.  A new model
family enters through a ``"builder": "module:function"`` key in its
configuration file, naming an importable function with this signature —
no ``model_config`` PR edits this file or ``run.py``.
"""

from __future__ import annotations

import importlib
import json

import numpy as np

# The CPU rehearsal's sizes (--cpu-dryrun): the same kernels' geometry
# (head_dim 128, page 128, chunk 128) in the Pallas interpreter, toy
# everything else.  It proves the harness, never a speed.
TOY = {"hidden_size": 512, "intermediate_size": 512, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512}
TOY_ENGINE = {"max_seq": 512, "num_blocks": 25, "max_batch": 4}


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def toy_config(config: dict) -> dict:
    out = dict(config)
    out.update(TOY)
    out["engine"] = {**config["engine"], **TOY_ENGINE}
    return out


def resolve(spec: str):
    """``module:function`` -> the function."""
    mod, _, fn = spec.partition(":")
    return getattr(importlib.import_module(mod), fn)


def weight_key(seed: int):
    """The key the weights derive from: the one recipe the benchmark
    states, kept with the reference (seeds may pass 2**31)."""
    from benchmarks.reference.llama_dense import weight_key as key

    return key(seed)


def llama_config(config: dict):
    import jax.numpy as jnp

    from triton_dist_tpu.models.llama import LlamaConfig

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if config.get("sliding_window"):
        raise ValueError("this builder serves full attention only")
    cfg = LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        max_seq=config["engine"]["max_seq"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=dtypes[config["torch_dtype"]])
    if cfg.head_dim != config.get("head_dim", cfg.head_dim):
        raise ValueError("head_dim must be hidden_size / heads here")
    return cfg


def build_llama(config: dict, seed: int, *, chips: int, ladder: list,
                interpret: bool = False):
    """-> (engine, LlamaConfig).  Weights first, pools second
    (``init_params`` holds float32 transients while it draws).  Every key
    of the file's ``engine`` group is passed to a constructor here; what
    the file does not name is the engine's default."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models.generate import Generator
    from triton_dist_tpu.serve import ServeEngine

    if chips != 1:
        raise ValueError("this builder places the model on one chip; a "
                         "mesh cell names its own builder")
    cfg = llama_config(config)
    eng = dict(config["engine"])
    kv = {"bfloat16": False, "int8": True}[eng.pop("kv_dtype")]
    max_seq = eng.pop("max_seq")
    params = llama.init_params(cfg, weight_key(seed))
    jax.block_until_ready(params)
    gen = Generator(cfg, Mesh(np.array(jax.devices()[:1]), ("sp",)),
                    axis="sp", max_seq=max_seq, interpret=interpret,
                    kv_dtype=jnp.int8 if kv else None)
    engine = ServeEngine(gen, params, bucket_ladder=ladder, **eng)
    if engine.kv_quant != kv:
        raise ValueError(f"the file states kv_dtype "
                         f"{config['engine']['kv_dtype']} and the engine "
                         f"built {'int8' if engine.kv_quant else 'float'} "
                         f"pools")
    return engine, cfg


def reachable_ladder(config: dict, prompt_lengths) -> list:
    """The rungs of the engine's default scratch-extent ladder that this
    traffic's prompts can reach (the engine closes any ladder with its cap
    rung itself): warm-up compiles these and no others."""
    from triton_dist_tpu.serve.engine import build_bucket_ladder

    eng = config["engine"]
    page, chunk = eng["page_size"], eng["prefill_chunk"]
    full = build_bucket_ladder(max(page, chunk), eng["max_seq"], page)
    rungs = set()
    for n in prompt_lengths:
        need = max(-(-n // page) * page, -(-n // chunk) * chunk)
        rungs.add(next(r for r in full if r >= need))
    return sorted(rungs)


def build(config: dict, seed: int, **kw):
    fn = resolve(config["builder"]) if "builder" in config else build_llama
    return fn(config, seed, **kw)
