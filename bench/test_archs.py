"""Every configuration resolves, by its ``model_type`` and its
``reference``, to modules that keep the contract the harness reads; what
the program cannot run is refused."""
import json

import pytest

from bench import spec, system

CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))
ARCH = ("check", "model_config", "layer_matrix_params", "head_params",
        "decode_weight_bytes", "kv_bytes_per_token", "attention_flops")
REFERENCE = ("served_gaps", "control_gaps", "weights")
NO_PATH = "the program has no path for this configuration as its file states it"


def _cfg(name):
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_config_resolves_to_an_architecture_and_a_reference(name):
    cfg = _cfg(name)
    arch = system.arch(cfg)
    assert all(callable(getattr(arch, f)) for f in ARCH)
    assert arch.TINY
    ref = spec.load_reference(cfg["reference"])
    assert all(callable(getattr(ref, f)) for f in REFERENCE)
    assert system.model_config(cfg).name == name


@pytest.mark.parametrize("model_type", ["gpt2", "../archs/mistral", "",
                                        "__init__"])
def test_an_unknown_model_type_is_refused(model_type):
    cfg = dict(_cfg(CONFIGS[0]), model_type=model_type)
    with pytest.raises(ValueError, match=NO_PATH):
        system.model_config(cfg)


def test_a_tied_head_is_refused():
    """``decode_weight_bytes`` counts the head apart from the embedding,
    and the dense reference has no tied head."""
    cfg = dict(_cfg("mistral-7b-v0.3-h16"), tie_word_embeddings=True)
    with pytest.raises(ValueError, match=NO_PATH):
        system.model_config(cfg)
