"""The warm-up builds every program a cell's window can drive, and no
prefill-chunk program larger than the cell's mix can reach."""
import numpy as np
import pytest

from bench import harness, system, tiny, traffic

SEED = 2**31 + 21


@pytest.mark.parametrize("chunk,min_prompt,rows", [
    (512, 1024, 2), (512, 512, 2), (512, 32, 17), (256, 40, 8), (32, 8, 5),
    (8, 1, 8)])
def test_chunk_rows_max(chunk, min_prompt, rows):
    assert harness.chunk_rows_max(chunk, min_prompt, 32) == min(rows, 32)


def _recording(engine, seen):
    backend = engine.backend
    chunk, decode = backend.prefill_chunk, backend.decode

    def prefill_chunk(toks, offs, clens, slots):
        seen["chunk"].add(toks.shape[0])
        seen["rows"] = max(seen["rows"], int(np.sum(slots >= 0)))
        return chunk(toks, offs, clens, slots)

    def dec(slot_tokens, active_idx, bucket):
        seen["decode"].add(bucket)
        return decode(slot_tokens, active_idx, bucket)
    backend.prefill_chunk, backend.decode = prefill_chunk, dec


@pytest.mark.parametrize("min_prompt", [8, 40])
def test_warm_up_builds_each_reachable_bucket_and_the_window_no_other(
        min_prompt):
    cell = tiny.cell()
    mix = dict(cell.mix, prompt=dict(cell.mix["prompt"], min=min_prompt))
    chunk = cell.config["engine"]["prefill_chunk"]
    harness.prepare(require_tpu=False)
    sys_ = system.build(cell.config, SEED)
    engine = sys_.engine
    warm = dict(chunk=set(), decode=set(), rows=0)
    _recording(engine, warm)
    harness.warm_up(engine, cell.config["vocab_size"], chunk, min_prompt)
    rows = harness.chunk_rows_max(chunk, min_prompt, engine.N)
    buckets = engine._buckets
    reach = next(b for b in buckets if b >= rows)
    assert warm["chunk"] == {b for b in buckets if b <= reach}
    assert warm["decode"] == set(buckets)
    assert warm["rows"] == (rows if rows > buckets[0] else 1)

    window = dict(chunk=set(), decode=set(), rows=0)
    engine = sys_.fresh_engine()
    _recording(engine, window)
    harness.serve(engine, mix, SEED, 2.0, cell.config["vocab_size"],
                  counter=harness.CompileCounter())
    assert window["rows"] <= rows
    assert window["chunk"] <= warm["chunk"]
    sys_.close()
    assert all(q.prompt.size >= min_prompt
               for q in traffic.schedule(mix, SEED, 2.0, 512))
