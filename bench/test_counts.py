"""Operation and byte counts against the program's own parameter shapes,
and the peaks table."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import counts, peaks, spec, system

CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))


def _cfg(name):
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


# every token of a dense model passes through every matrix, so its counts
# follow from the program's parameter shapes alone; an architecture of
# another family brings such a test of its own
DENSE = [c for c in CONFIGS
         if system.model_config(_cfg(c)).family == "dense"]


def _param_shapes(cfg):
    from repro.models import init_params, split_params

    mcfg = system.model_config(cfg)
    return jax.eval_shape(lambda k: split_params(init_params(mcfg, k))[0],
                          jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", DENSE)
def test_streamed_weight_bytes_are_all_leaves_but_the_embedding(name):
    cfg = _cfg(name)
    arch = system.arch(cfg)
    shapes = _param_shapes(cfg)
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    emb = shapes["embed"].size * shapes["embed"].dtype.itemsize
    for rows in (1, 32):
        assert arch.decode_weight_bytes(cfg, rows) == total - emb
    blocks = shapes["blocks"]
    kv = 2 * blocks["attn"]["wk"].shape[0] * np.prod(
        blocks["attn"]["wk"].shape[2:]) * 2
    assert arch.kv_bytes_per_token(cfg) == kv


@pytest.mark.parametrize("name", DENSE)
def test_decode_flops_count_every_matrix_once_per_row(name):
    cfg = _cfg(name)
    shapes = _param_shapes(cfg)
    matrices = sum(s.size for path, s in
                   jax.tree_util.tree_leaves_with_path(shapes)
                   if "embed" not in jax.tree_util.keystr(path)
                   and "norm" not in jax.tree_util.keystr(path))
    n_layers = cfg["num_hidden_layers"]
    assert counts.decode_step_flops(cfg, [1]) == pytest.approx(
        2 * matrices + n_layers * system.arch(cfg).attention_flops(cfg, 1))
    f2, b2 = counts.paged_attn_call(cfg, [100, 300])
    assert f2 == 4 * cfg["num_attention_heads"] * spec.head_dim(cfg) * 400
    assert b2 > 400 * 2 * cfg["num_key_value_heads"] * spec.head_dim(cfg) * 2


def test_mistral_counts_are_the_ones_its_cells_were_measured_with():
    """The counts behind ``step_mfu``, ``decode_step_roofline`` and
    ``paged_attn_roofline``, as the cells' earlier readings used them."""
    cfg = _cfg("mistral-7b-v0.3-h16")
    arch = system.arch(cfg)
    assert arch.layer_matrix_params(cfg) == 218_103_808
    assert arch.decode_weight_bytes(cfg, 1) == 7_248_297_984
    assert arch.kv_bytes_per_token(cfg) == 65_536
    assert counts.decode_step_flops(cfg, [100, 300]) == 14_600_372_224
    assert counts.decode_step_bytes(cfg, [100, 300]) == 7_274_512_384
    assert counts.prefill_flops(cfg, 512) == 3_573_412_790_272
    assert counts.paged_attn_call(cfg, [100, 300]) == (6_553_600, 1_671_168)


def test_mistral_maps_onto_the_same_model_config():
    """Field by field: the keys the file sets, and the program's default
    for every other field."""
    mcfg = system.model_config(_cfg("mistral-7b-v0.3-h16"))
    want = dict(name="mistral-7b-v0.3-h16", family="dense", n_layers=16,
                d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336,
                vocab_size=32768, head_dim=128, rope_theta=1e6,
                norm_eps=1e-5, mlp_variant="swiglu", dtype="bfloat16",
                tie_embeddings=False,
                source="https://huggingface.co/mistralai/Mistral-7B-v0.3")
    for f in dataclasses.fields(mcfg):
        assert getattr(mcfg, f.name) == want.get(f.name, f.default), f.name


def test_roofline_and_peaks():
    v5e = peaks.for_device("TPU v5 lite")
    assert v5e.flops_per_s == 197e12 and v5e.hbm_bytes_per_s == 819e9
    assert counts.least_seconds(197e12, 0, v5e) == pytest.approx(1.0)
    assert counts.least_seconds(0, 819e9, v5e) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.for_device("TPU v9 imaginary")


def test_step_readers_on_a_log():
    """The model-step readers over a two-step window: one decode-only
    step and one with a prefill chunk."""
    import types

    cfg = _cfg(CONFIGS[0])
    log = types.SimpleNamespace(
        open_s=0.0, end_s=1.0, steps=[
            dict(t0=0.0, t1=0.05, prefill_tokens=0, decode_ctx=[100, 200]),
            dict(t0=0.05, t1=0.25, prefill_tokens=256, decode_ctx=[101])])
    run = types.SimpleNamespace(cell=types.SimpleNamespace(config=cfg),
                                log=log, peak=peaks.for_device("TPU v5e"))
    mfu = spec.load_reader("step_mfu")(run)
    flops = (counts.decode_step_flops(cfg, [100, 200])
             + counts.decode_step_flops(cfg, [101])
             + counts.prefill_flops(cfg, 256))
    assert mfu == pytest.approx(100 * flops / 197e12)
    roof = spec.load_reader("decode_step_roofline")(run)
    least = counts.decode_step_bytes(cfg, [100, 200]) / 819e9
    assert roof == pytest.approx(100 * least / 0.05)
    assert 0 < mfu < roof < 100
    run.peak = None
    assert spec.load_reader("step_mfu")(run) is None
