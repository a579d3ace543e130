"""The system under test, built from a configuration file.

This module and the architecture modules under ``archs/`` are the only
benchmark code that imports the program (``repro``, from ``src/``).  A
``configs/<name>.json`` is mapped onto the program's ``ModelConfig`` by
``archs/<model_type>.py`` (``arch``) and onto its ``EngineConfig`` here;
the weights are drawn with the program's own ``init_serving_params`` and
a ``ServingEngine`` is built on them.  A key the program cannot run as
the file states is an error.
"""
from __future__ import annotations

import dataclasses
import importlib

from .spec import BENCH, no_path

_ENGINE_ONLY = ("policy",)


def arch(cfg: dict):
    """The module ``archs/<model_type>.py`` of a configuration file; a
    name with a leading ``_`` (such as the package's ``__init__``) is no
    architecture."""
    name = cfg["model_type"]
    if not (name.isidentifier() and not name.startswith("_")
            and (BENCH / "archs" / f"{name}.py").is_file()):
        raise no_path(cfg)
    return importlib.import_module(f"bench.archs.{name}")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    a = arch(cfg)
    a.check(cfg)
    return a.model_config(cfg)


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
