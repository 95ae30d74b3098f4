"""`kv_read_share.*`: the reader over hand-made iteration records, the
parent's records that lack its fields, and the metrics' files."""
import pytest

from benchmark import manifest
from benchmark.readers import kv_read_share


def record(t_start, t_end, **fields):
    base = {"t_start": t_start, "t_end": t_end, "prefill_rows": 0,
            "prefill_tokens": 0, "decode_rows": 4, "tokens_emitted": 4,
            "queue_depth": 0, "active_slots": 4, "kv_blocks_held": 0,
            "kv_tokens_resident": 0, "kv_blocks_total": 64, "slots": 4,
            "block_size": 16, "host_s": {}}
    return {**base, **fields}


RECORDS = [
    record(0.5, 0.9, kv_pages_read=40, kv_pages_table=256),   # warm-up
    record(1.0, 1.2, kv_pages_read=10, kv_pages_table=256),
    record(1.2, 1.5, kv_pages_read=22, kv_pages_table=512),   # two steps
    record(1.5, 1.8, kv_pages_read=0, kv_pages_table=256),    # all muted
    record(1.8, 2.6, kv_pages_read=99, kv_pages_table=256),   # past the end
]


def ring(monkeypatch, records):
    from paddle_tpu import trace
    monkeypatch.setattr(trace, "iteration_records", lambda: list(records),
                        raising=False)


def window(**more):
    return {"t0": 1.0, "window_s": 1.0, "log": None, "sent": [], **more}


def test_share_is_pages_read_over_pages_of_the_tables(monkeypatch):
    ring(monkeypatch, RECORDS)
    assert kv_read_share.read(window()) == \
        pytest.approx(100.0 * (10 + 22 + 0) / (256 + 512 + 256))
    # a run that traced nothing reads the whole window either way
    assert kv_read_share.read(window(), before_slice=True) == \
        kv_read_share.read(window())


@pytest.mark.parametrize("records", [
    [{k: v for k, v in r.items() if not k.startswith("kv_pages")}
     for r in RECORDS],                      # the parent: no such fields
    [],                                      # no record at all
    [record(1.0, 1.2, kv_pages_read=0, kv_pages_table=0)],  # slab engine
], ids=["records_lack_the_fields", "no_records", "no_paged_step"])
def test_nothing_to_read_leaves_the_metric_out(monkeypatch, records):
    ring(monkeypatch, records)
    assert kv_read_share.read(window()) is None
    assert kv_read_share.read(window(), before_slice=True) is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    from paddle_tpu import trace
    monkeypatch.delattr(trace, "iteration_records")
    assert kv_read_share.read(window()) is None


def test_records_with_and_without_the_fields_mix(monkeypatch):
    old = {k: v for k, v in RECORDS[1].items()
           if not k.startswith("kv_pages")}
    ring(monkeypatch, [old, RECORDS[2]])
    assert kv_read_share.read(window()) == pytest.approx(100.0 * 22 / 512)


@pytest.mark.parametrize("name,moves,cell,args", [
    ("kv_read_share.batch", "serve_out_tok_s", "gpt2_medium.batch_gen_v2", {}),
    ("kv_read_share.gap", "gap_p95_ms", "gpt2_medium.long_in_open_v2",
     {"before_slice": True})])
def test_the_metrics_files_and_entries(name, moves, cell, args):
    listed = {m["name"]: m for m in manifest.benchmark_json()["per_layer"]}
    entry = listed[name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "paged KV",
                     "moves": moves}
    assert manifest.metric_file(name) == {"reader": "kv_read_share",
                                          "args": args}
    assert name in {m["name"] for m in manifest.metrics_of(cell, "per_layer")}
    other = "gpt2_medium.long_in_open_v2" if "batch" in name \
        else "gpt2_medium.batch_gen_v2"
    assert name not in {m["name"]
                        for m in manifest.metrics_of(other, "per_layer")}
    assert name not in {m["name"] for m in manifest.metrics_of(
        "bert_base_nodropout.pretrain", "per_layer")}
