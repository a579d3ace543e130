"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program (``repro``,
from ``src/``): it maps a ``configs/<name>.json`` onto the program's
``ModelConfig`` and ``EngineConfig``, draws the weights with the
program's own ``init_serving_params`` and builds a ``ServingEngine``.
A key the program cannot run as the file states is an error.
"""
from __future__ import annotations

import dataclasses

from .spec import head_dim

# the file's hidden_act -> the program's mlp_variant
_MLP = {"silu": "swiglu"}
_ENGINE_ONLY = ("policy",)
# architectures whose layer equations the program runs: pre-norm RMSNorm,
# grouped-query attention with full rotary positions and no biases
_ARCH = ("mistral", "llama")


def _check(cfg: dict) -> None:
    """Refuse a file that states something the program cannot run."""
    ok = (cfg["model_type"] in _ARCH and cfg["hidden_act"] in _MLP
          and cfg.get("sliding_window") is None
          and not cfg.get("attention_bias", False)
          and not cfg.get("mlp_bias", False)
          and cfg.get("rope_scaling") is None
          and cfg["torch_dtype"] == "bfloat16")
    if not ok:
        raise ValueError(f"{cfg['name']}: the program has no path for this "
                         "configuration as its file states it")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    _check(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        mlp_variant=_MLP[cfg["hidden_act"]], dtype=cfg["torch_dtype"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


def engine_config(cfg: dict):
    from repro.serving import EngineConfig

    kw = {k: v for k, v in cfg["engine"].items() if k not in _ENGINE_ONLY}
    ec = EngineConfig(**kw)
    if ec.max_seq_len > cfg["max_position_embeddings"]:
        raise ValueError("max_seq_len beyond the model's context")
    return ec


@dataclasses.dataclass
class System:
    """Weights and engine of one run; ``close`` frees their device memory."""
    cfg: dict
    mcfg: object
    ecfg: object
    params: object
    engine: object = None

    def fresh_engine(self):
        """A new engine (empty pool and queue) on the same weights; the
        programs it runs are the ones already compiled."""
        from repro.core import make_policy
        from repro.launch.mesh import make_local_mesh
        from repro.serving import ServingEngine

        self.engine = None          # free the old pool first
        self.engine = ServingEngine(self.mcfg, self.params, self.ecfg,
                                    make_policy(self.cfg["engine"]["policy"]),
                                    mesh=make_local_mesh())
        return self.engine

    def close(self) -> None:
        self.engine = None
        self.params = None


def build(cfg: dict, seed: int) -> System:
    """Weights from ``seed`` (one jitted draw on the device, in the served
    dtype) and a fresh engine on the first device."""
    import jax

    from repro.launch.serve import init_serving_params

    mcfg, ecfg = model_config(cfg), engine_config(cfg)
    params = init_serving_params(mcfg, seed)
    jax.block_until_ready(params)
    system = System(cfg, mcfg, ecfg, params)
    system.fresh_engine()
    return system


def new_request(rid: int, prompt, max_new_tokens: int):
    from repro.serving import ServeRequest

    return ServeRequest(rid=rid, tokens=prompt, max_new_tokens=max_new_tokens)
