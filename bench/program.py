"""The bridge to the program under test: its configuration of a model,
held to the sizes that the benchmark's configuration file states."""
from __future__ import annotations


def program_config(spec: dict):
    """The program's configuration of ``spec["name"]`` with its MLP set as
    the file states (the program's olmo_1b defaults to a plain GELU MLP;
    the published model's is SwiGLU), checked against every stated size."""
    from dataclasses import replace

    from repro.configs import get_config

    cfg = replace(get_config(spec["name"]), gated=spec["gated"])
    stated = {"n_layers": spec["n_layers"], "d_model": spec["d_model"],
              "n_heads": spec["n_heads"], "n_kv_heads": spec["n_kv_heads"],
              "hd": spec["head_dim"], "d_ff": spec["d_ff"],
              "vocab": spec["vocab"], "gated": spec["gated"],
              "tie_embeddings": spec["tie_embeddings"],
              "rope_theta": spec["rope_theta"],
              "param_dtype": spec["param_dtype"],
              "dtype": spec["compute_dtype"]}
    got = {k: getattr(cfg, k) for k in stated}
    if got != stated:
        raise RuntimeError(f"program config {cfg.name} differs from the "
                           f"benchmark's: {got} != {stated}")
    return cfg
