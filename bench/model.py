"""The system under test: a configuration file turned into the program's
``ModelConfig``, and the engine and scheduler the window drives."""
from __future__ import annotations

from bench.weights import dims


def model_config(cfg: dict):
    """The program's ModelConfig for a LLaMA-family dense decoder file."""
    from repro.models.transformer import BlockSpec, ModelConfig
    m = dims(cfg)
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU (silu) MLPs are "
                         f"wired here, got {cfg['hidden_act']!r}")
    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError(f"{cfg['name']}: the program's RMSNorm runs "
                         f"eps=1e-6, the file says {cfg['rms_norm_eps']}")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["h"], n_kv=m["kv"], head_dim=m["hd"], d_ff=m["ff"],
        vocab=m["V"], qkv_bias=m["qkv_bias"],
        pattern=(BlockSpec(kind="attn", attn_type="global", mlp="swiglu"),),
        rope_theta=m["theta"], quant=m["quant"],
        compute_dtype=cfg["serving"]["compute_dtype"])


# keys of a mix's ``serve`` that go to the Scheduler, not to ServeConfig
SCHEDULER_KEYS = ("slots", "decode_chunk")


def serve_config(traffic: dict, seed: int):
    """The program's ServeConfig from a mix's ``serve`` keys (every key but
    the Scheduler's is a ServeConfig field); the seed is the run's."""
    import dataclasses

    from repro.serve import ServeConfig
    keys = {k: v for k, v in traffic["serve"].items()
            if k not in SCHEDULER_KEYS}
    fields = {f.name for f in dataclasses.fields(ServeConfig)} - {"seed"}
    unknown = sorted(set(keys) - fields)
    if unknown:
        raise ValueError(f"serve keys {unknown} are not ServeConfig fields "
                         f"(have {sorted(fields)})")
    return ServeConfig(**keys, seed=int(seed) & 0x7FFFFFFF)
