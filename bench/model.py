"""The system under test: a configuration file turned into the program's
``ModelConfig``, and the engine and scheduler the window drives."""
from __future__ import annotations

from bench import spec


def model_config(cfg: dict):
    """The program's ModelConfig, as the configuration's architecture
    builds it."""
    return spec.arch(cfg).model_config(cfg)


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
