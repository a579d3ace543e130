"""The sparse-expert configuration against its plain reference on the
CPU at the tiny size: the dropless expert layer, the held share, the
QK-norm, the counts, and the three readers of the expert counters."""
import dataclasses
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, peaks, spans, spec, system, tiny
from bench.test_spans import _ev, _plane

NAME = "qwen3-30b-a3b-e32-h24"
SEED = 2**31 + 7


def _cfg():
    return json.loads((spec.BENCH / "configs" / f"{NAME}.json").read_text())


def _ref():
    return spec.load_reference(_cfg()["reference"])


def _mesh():
    from repro.launch.mesh import make_local_mesh

    return make_local_mesh()


def _dropless(h, valid, ffn, cfg, offset=0):
    from repro.models.moe import moe_ffn_dropless

    return jax.jit(functools.partial(
        moe_ffn_dropless, k=cfg["num_experts_per_tok"], mesh=_mesh(),
        batch_axes=("data",), expert_offset=offset))(h, valid, ffn)


def _tiny(**keys):
    return dict(tiny.config(NAME), **keys)


def _program_ffn(cfg, offset, seed=SEED):
    """Layer 0's expert weights as the program draws them in float32."""
    from repro.models import init_params, split_params

    mcfg = dataclasses.replace(system.model_config(cfg), dtype="float32",
                               expert_offset=offset)
    params = jax.jit(lambda k: split_params(init_params(mcfg, k))[0])(
        jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a[0], params["blocks"]["ffn"])


def test_dropless_layer_matches_the_reference_layer_with_padding():
    cfg = _tiny()
    w = jax.tree.map(lambda a: a[0],
                     _ref().weights(cfg, SEED)["blocks"]["ffn"])
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 6, cfg["hidden_size"]))
    # row 1 is a padding row; row 0 has padding columns past 4
    valid = jnp.asarray(np.array([[1, 1, 1, 1, 0, 0], [0] * 6, [1] * 6],
                                 bool))
    out, _ = _dropless(h, valid, w, cfg)
    live = np.asarray(valid)
    want = _ref().expert_layer(h[live], w, cfg["num_experts_per_tok"], 0)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert not np.asarray(out)[~live].any()
    # a live token's result does not depend on what else is in the batch
    alone, _ = _dropless(h[2:3], valid[2:3], w, cfg)
    np.testing.assert_array_equal(np.asarray(alone)[0], np.asarray(out)[2])


def test_held_shares_sum_to_the_uncut_layer():
    """Four chips of two experts each: the router is the same on each
    (counted once), each holds the uncut layer's rows of its experts,
    and the parts they compute sum to the layer of all eight."""
    cfg = _tiny(num_experts=2)
    whole_cfg = _tiny(num_experts=8)
    whole = _program_ffn(whole_cfg, 0)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg["hidden_size"]))
    valid = jnp.ones((2, 5), bool)
    parts = []
    for offset in (0, 2, 4, 6):
        share = _program_ffn(cfg, offset)
        np.testing.assert_array_equal(share["router"], whole["router"])
        for name in ("w1", "w3", "w2"):
            np.testing.assert_array_equal(share[name],
                                          whole[name][offset:offset + 2])
        parts.append(_dropless(h, valid, share, cfg, offset)[0])
    uncut = _ref().expert_layer(h.reshape(10, -1), whole,
                                cfg["num_experts_per_tok"], 0)
    np.testing.assert_allclose(np.asarray(sum(parts)).reshape(10, -1),
                               np.asarray(uncut), rtol=2e-5, atol=2e-6)
    whole_out, _ = _dropless(h, valid, whole, whole_cfg)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole_out),
                               rtol=2e-5, atol=2e-6)


def test_qk_norm_matches_the_reference():
    """The program's forward over one prompt, on the reference's weights
    in float32, gives the reference's logits; without its QK-norm it
    does not."""
    from repro.models import prefill_fn

    cfg = _tiny()
    ref = _ref()
    params = ref.weights(cfg, SEED)
    prompt = np.random.default_rng(3).integers(1, cfg["vocab_size"], 40)
    x = np.asarray(ref.final_hidden(cfg, SEED, [(prompt, np.array([1]))])[0])
    last = x[prompt.size - 1]
    last = last / np.sqrt(np.mean(last * last) + cfg["rms_norm_eps"])
    want = last @ np.asarray(params["lm_head"])
    mcfg = dataclasses.replace(system.model_config(cfg), dtype="float32")
    batch = {"tokens": jnp.asarray(prompt[None], jnp.int32)}

    def logits(m):
        return np.asarray(prefill_fn(m, params, batch, max_len=64,
                                     mesh=_mesh())[0])[0]

    np.testing.assert_allclose(logits(mcfg), want, rtol=1e-4, atol=1e-4)
    plain = logits(dataclasses.replace(mcfg, qk_norm=False))
    assert np.abs(plain - want).max() > 1e-2


def test_counts_are_the_cut_configurations():
    """The numbers the cell's counts rest on (PERF.md section 4)."""
    cfg = _cfg()
    arch = system.arch(cfg)
    assert arch.held_layer_params(cfg) == 170_131_456
    assert arch.kv_bytes_per_token(cfg) == 49_152
    assert arch.layer_matrix_params(cfg) == 28_573_696
    assert arch.experts_per_token(cfg) == 2.0
    assert arch.experts_hit(cfg, 32) == pytest.approx(
        32 * (1 - (120 / 128) ** 32))
    assert arch.gmm_call(cfg, 64, 28) == (
        6 * 2048 * 768 * 64, 28 * 3 * 2048 * 768 * 2 + 64 * 2 * 2048 * 2)
    assert counts.prefill_flops(cfg, 512) == 2 * 24 * 28_573_696 * 512


def test_held_parameters_are_the_program_shapes():
    from repro.models import init_params, split_params

    cfg = _cfg()
    mcfg = system.model_config(cfg)
    shapes = jax.eval_shape(lambda k: split_params(init_params(mcfg, k))[0],
                            jax.random.PRNGKey(0))
    blocks = shapes["blocks"]
    matrices = sum(s.size for path, s in
                   jax.tree_util.tree_leaves_with_path(blocks)
                   if "norm" not in jax.tree_util.keystr(path))
    arch = system.arch(cfg)
    assert matrices == 24 * arch.held_layer_params(cfg)
    assert blocks["ffn"]["w1"].shape == (24, 32, 2048, 768)
    assert blocks["ffn"]["router"].shape == (24, 2048, 128)
    assert blocks["attn"]["q_norm"].shape == (24, 128)
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    emb = shapes["embed"].size * shapes["embed"].dtype.itemsize
    experts = 24 * 32 * arch.expert_params(cfg) * 2
    # a step that hits every held expert reads every leaf but the table
    assert arch.decode_weight_bytes(cfg, 10**6) == pytest.approx(
        total - emb, rel=1e-12)
    assert arch.decode_weight_bytes(cfg, 32) == pytest.approx(
        total - emb - experts * (120 / 128) ** 32)


@pytest.mark.parametrize("key,value", [("norm_topk_prob", False),
                                       ("mlp_only_layers", [3]),
                                       ("shared_expert_intermediate_size",
                                        768),
                                       ("sliding_window", 4096),
                                       ("rope_scaling", {"type": "yarn"}),
                                       ("tie_word_embeddings", True),
                                       ("expert_offset", 100)])
def test_what_the_program_cannot_run_is_refused(key, value):
    with pytest.raises(ValueError, match="has no path"):
        system.model_config(dict(_cfg(), **{key: value}))


def test_a_program_without_qk_norm_or_held_experts_has_no_path(
        monkeypatch):
    """A program older than these fields refuses the file at once,
    before any weight is drawn."""
    import dataclasses as dc

    import repro.configs.base as base

    old = dc.make_dataclass("ModelConfig", [
        (f.name, f.type, dc.field(default=f.default))
        for f in dc.fields(base.ModelConfig)
        if f.name not in ("qk_norm", "experts_held", "expert_offset")])
    monkeypatch.setattr(base, "ModelConfig", old)
    with pytest.raises(ValueError, match="has no path"):
        system.model_config(_cfg())


def _moe_planes():
    """Two decode steps and one chunk, their expert counters on the
    spans; three grouped-matmul kernels of 1 us (two expert-weight
    slices and a KV-pool slice are none of them)."""
    host = _plane("/host:CPU", {"python": [
        _ev("engine.step", 1000, 9000, step_num=0),
        _ev("serve.step", 1000, 9000, waiting=3, active=32),
        _ev("serve.chunk", 1100, 3000, rows=1, bucket=1, tokens=512,
            chunk=512, moe_pairs=24 * 1024, moe_hit=24 * 32,
            moe_max=24 * 50),
        _ev("serve.decode", 3000, 6000, rows=32, bucket=32, tokens=32,
            moe_pairs=24 * 64, moe_hit=24 * 28, moe_max=24 * 5),
        _ev("serve.decode", 6000, 9000, rows=32, bucket=32, tokens=32,
            moe_pairs=24 * 64, moe_hit=24 * 27, moe_max=24 * 6)]})
    ops = [_ev("%gmm.1 = f32[256,768]{1,0} custom-call()", 2000, 3000),
           _ev("%dynamic-slice_bitcast_fusion.4 = bf16[32,2048,768]{2,1,0}"
               " fusion(bf16[24,32,2048,768]{3,2,1,0} %p)", 3000, 3500),
           _ev("%gmm.2 = f32[256,768]{1,0} custom-call()", 4000, 5000),
           _ev("%dynamic-slice_bitcast_fusion.5 = bf16[32,768,2048]{2,1,0}"
               " fusion(bf16[24,32,768,2048]{3,2,1,0} %p)", 6000, 6500),
           _ev("%dynamic-slice_bitcast_fusion.6 = bf16[3072,16,4,128]"
               "{3,2,1,0} fusion(bf16[24,3072,16,4,128]{4,3,2,1,0} %p)",
               6500, 7000),
           _ev("%gmm.3 = f32[256,2048]{1,0} custom-call()", 7000, 8000),
           _ev("%fusion.1 = bf16[4]{0} fusion()", 8000, 8500)]
    return [host, _plane("/device:TPU:0", {"XLA Ops": ops,
                                           "XLA Modules": []})]


def test_expert_readers_on_a_known_trace(monkeypatch):
    from bench import trace

    found = spans.reduce(_moe_planes())
    monkeypatch.setattr(spans, "for_run", lambda run: found)
    cfg = _cfg()
    v5e = peaks.for_device("TPU v5e")
    run = types.SimpleNamespace(cell=types.SimpleNamespace(config=cfg),
                                trace=trace.reduce(_moe_planes()), peak=v5e)
    read = {m: spec.load_reader(m) for m in
            ("moe_gmm_roofline", "moe_gmm_share", "expert_load_max_mean")}
    gmm = system.arch(cfg).gmm_call
    least = sum(counts.least_seconds(*gmm(cfg, p, h), v5e) for p, h in
                ((24 * 1024, 24 * 32), (24 * 64, 24 * 28),
                 (24 * 64, 24 * 27)))
    assert read["moe_gmm_roofline"](run) == pytest.approx(
        100 * least / 3e-6)
    assert read["moe_gmm_share"](run) == pytest.approx(100 * 3000 / 8000)
    assert read["expert_load_max_mean"](run) == pytest.approx(
        24 * 11 * 32 / (24 * 128))


def test_expert_readers_find_nothing_without_counters(monkeypatch):
    """A program without expert counters (a dense model, or the program
    before them) gives no reading, and raises nothing."""
    from bench.test_spans import _planes

    found = spans.reduce(_planes())
    monkeypatch.setattr(spans, "for_run", lambda run: found)
    from bench import trace

    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(config=_cfg()),
        trace=trace.reduce(_planes()), peak=peaks.for_device("TPU v5e"))
    for name in ("moe_gmm_roofline", "moe_gmm_share",
                 "expert_load_max_mean"):
        assert spec.load_reader(name)(run) is None, name
