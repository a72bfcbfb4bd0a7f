"""The chip benchmark's arithmetic, checked without a chip: traffic
generation, end-to-end statistics, operation and byte counts, the peaks
table, and finding configurations, mixes and metrics by name."""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np
import pytest

from chipbench import files, flops, peaks, stats
from chipbench import traffic as T

HERE = pathlib.Path(__file__).resolve().parent
GRANITE = files.config("granite-8b")["model"]


def test_same_seed_same_requests_and_lengths_rounded():
    tr = files.traffic("chat")
    a = T.plan(tr, 2 ** 31 + 12345, 10.0, 49152)
    b = T.plan(tr, 2 ** 31 + 12345, 10.0, 49152)
    assert [(p.due_s, p.prompt_len, p.max_new, p.tokens) for p in a] == \
        [(p.due_s, p.prompt_len, p.max_new, p.tokens) for p in b]
    assert {p.prompt_len for p in a} <= set(tr["prompt"]["round_up_to"])
    assert all(tr["output"]["lo"] <= p.max_new <= tr["output"]["hi"]
               for p in a)


def test_seeds_serve_the_same_schedule_with_other_tokens():
    tr = files.traffic("rag")
    a, b = T.plan(tr, 1, 10.0, 100), T.plan(tr, 2, 10.0, 100)
    assert [(p.due_s, p.prompt_len, p.max_new) for p in a] == \
        [(p.due_s, p.prompt_len, p.max_new) for p in b]
    assert a[0].tokens != b[0].tokens
    assert len(a) >= tr["arrivals"]["rate_per_s"] * (10.0 + tr["lead_s"])
    assert a[-1].due_s > 10.0 + tr["lead_s"]          # covers the window


def test_round_up_and_its_refusal():
    got = T.round_up(np.array([1, 256, 257, 2048]), [256, 512, 1024, 2048])
    assert got.tolist() == [256, 256, 512, 2048]
    with pytest.raises(ValueError):
        T.round_up(np.array([2049]), [256, 2048])


def test_sessions_stagger_first_answers_and_keep_each_client_shape():
    tr = files.traffic("longctx")
    plans = T.plan(tr, 5, 10.0, 1000)
    s = tr["sessions"]
    assert len(plans) == len(s["contexts"]) * len(s["max_new"])
    assert [p.max_new for p in plans] == s["first_max_new"]
    assert len(set(s["first_max_new"])) == len(plans)      # ends spread out
    c, n = T.clients(tr)[3]
    nxt = T.next_session(tr, 3, 99, 5, 1000)
    assert (nxt.rid, nxt.prompt_len, nxt.max_new) == (99, c, n)
    assert nxt.prompt_len == plans[3].prompt_len
    assert nxt.tokens != plans[3].tokens
    assert T.warm_shapes(tr) == [(c, 1) for c in s["contexts"]]


def test_ttft_from_due_time_with_missing_and_unfinished():
    seen = [stats.Seen(0, due=1.0, token_times=[1.5, 1.6, 1.8]),
            stats.Seen(1, due=2.0, token_times=[2.1]),
            stats.Seen(2, due=3.0, failed=True),
            stats.Seen(3, due=4.0),                     # no token by 10
            stats.Seen(4, due=11.0, token_times=[11.2])]  # due after close
    tt = stats.ttfts(seen, 0.0, 10.0)
    assert tt[:2] == pytest.approx([0.5, 0.1])
    assert tt[2] == math.inf and tt[3] == pytest.approx(6.0)
    assert len(tt) == 4
    c = stats.counts(seen, 0.0, 10.0)
    assert c == {"attempted": 4, "failed": 1, "first_token_in_window": 2}


def test_rates_and_gaps_over_the_whole_window():
    seen = [stats.Seen(0, due=0.0, token_times=[1.0, 2.0, 4.0, 12.0]),
            stats.Seen(1, due=0.0, token_times=[3.0, 3.5])]
    e = stats.end_to_end(seen, 0.0, 10.0)
    assert e["output_tok_s"] == pytest.approx(5 / 10.0)
    gaps = sorted(stats.gaps_in_window(seen, 0.0, 10.0))
    assert gaps == pytest.approx([0.5, 1.0, 2.0])       # 4.0 -> 12.0 is out
    assert e["itl_p50_ms"] == pytest.approx(1000.0)
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)


def test_flops_and_bytes_at_granite_widths():
    m = GRANITE
    per_layer = 4096 * 128 * (2 * 32 + 2 * 8) + 3 * 4096 * 14336
    assert flops.layer_params(m) == per_layer == 218_103_808
    step = flops.decode_step_flops(m, [100, 300])
    want = 2 * (16 * per_layer + 4096 * 49152) * 2 \
        + 16 * 4 * 32 * 128 * (100 + 300)
    assert step == want
    # prefill: body, causal attention (s(s+1)/2 pairs), last-position head
    pre = flops.prefill_flops(m, 1, 4)
    assert pre == 2 * 16 * per_layer * 4 + 16 * 4 * 32 * 128 * 10 \
        + 2 * 4096 * 49152
    pa = flops.paged_attention_cost(m, [10, 20])
    assert pa["flops"] == 4 * 32 * 128 * 30
    assert pa["bytes"] == 2 * 8 * 128 * 2 * 30 + 2 * 32 * 128 * (2 + 4)
    fa = flops.flash_attention_cost(m, 2, 8)
    assert fa["flops"] == 2 * 4 * 32 * 128 * 36
    assert fa["bytes"] == 2 * 8 * 128 * (64 + 16) * 2
    roof = flops.roofline_seconds(pa, peaks.peaks_for("TPU v5 lite"))
    assert roof["bound"] == "bytes"
    assert roof["seconds"] == pytest.approx(pa["bytes"] / 819e9)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flop_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def test_benchmark_names_files_that_exist():
    bench = files.benchmark()
    for c in bench["configs"]:
        assert files.config(c["name"])["name"] == c["name"]
        assert (files.ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert files.traffic(w["traffic"])["kind"] in ("open_loop",
                                                       "sessions")
    for m in bench["per_layer"]:
        mod = files.metric_reader(m["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == \
            (m["unit"], m["layer"], m["moves"], m["source"])


def test_added_files_are_found_by_name(tmp_path):
    """A later change adds a configuration, a mix and a metric as files."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "dummy.json").write_text(
        json.dumps({"name": "dummy", "model": {"num_layers": 1}}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"name": "burst", "kind": "open_loop"}))
    (tmp_path / "metrics" / "dummy_share.py").write_text(
        'NAME = "dummy_share"\nUNIT = "%"\nLAYER = "kernels"\n'
        'MOVES = "itl_p50_ms"\nSOURCE = "device_trace"\n'
        'def read(ctx):\n    return ctx.value\n')
    assert files.config("dummy", tmp_path)["model"]["num_layers"] == 1
    assert files.traffic("burst", tmp_path)["kind"] == "open_loop"
    bench = {"per_layer": [
        {"name": "dummy_share", "unit": "%", "workloads": ["d.burst"]}]}
    from chipbench import run
    ctx = type("Ctx", (), {"value": 42.0})()
    assert run.per_layer("d.burst", bench, ctx, tmp_path) == \
        {"dummy_share": {"value": 42.0, "unit": "%"}}
    assert run.per_layer("other.cell", bench, ctx, tmp_path) == {}


def test_window_host_numbers_count_queued_requests_and_gaps():
    from types import SimpleNamespace

    from chipbench import run
    from chipbench.serve import StepRecord
    drv = SimpleNamespace(
        seen={0: stats.Seen(0, due=1.0, submitted=1.5),
              1: stats.Seen(1, due=2.0),                 # queued at close
              2: stats.Seen(2, due=11.0, submitted=11.0)},
        records=[StepRecord(0.5, 0.6, "decode", [3], None, 2),
                 StepRecord(0.8, 1.0, "admit", [], (1, 8), 1),
                 StepRecord(1.2, 1.3, "decode", [4], None, 1),
                 StepRecord(9.9, 10.1, "decode", [5], None, 1)])  # crosses
    h = run.window_host_numbers(drv, 0.0, 10.0, 0, [0.002, 0.0005])
    assert sorted(h["submit_lags_s"]) == pytest.approx([0.5, 8.0])
    assert h["decode_tokens"] == [2, 1]
    c = h["calls"]
    assert (c["decode_steps"], c["admissions"], c["gc_pauses"]) == (2, 1, 2)
    assert c["max_decode_ms"] == pytest.approx(100.0)
    assert c["max_between_calls_ms"] == pytest.approx(200.0)
    assert c["max_gc_ms"] == pytest.approx(2.0)
    assert c["submit_lag_p95_ms"] == pytest.approx(
        1e3 * stats.percentile([0.5, 8.0], 95))


def test_chat_itl_tail_reads_the_window_gaps():
    """The per-layer ITL p95 reads every gap of the window (host clock)
    and finds nothing to read where the window held no two tokens."""
    from types import SimpleNamespace

    from chipbench import run
    seen = {0: stats.Seen(0, due=0.0, token_times=[0.1, 0.2, 0.5, 11.0]),
            1: stats.Seen(1, due=0.0, token_times=[1.0, 1.3])}
    drv = SimpleNamespace(seen=seen, records=[])
    h = run.window_host_numbers(drv, 0.0, 10.0, 0, [])
    assert sorted(h["itl_gaps_s"]) == pytest.approx([0.1, 0.3, 0.3])
    mod = files.metric_reader("itl_p95_ms.chat")
    ctx = SimpleNamespace(host=h)
    assert mod.read(ctx) == pytest.approx(
        1e3 * stats.percentile([0.1, 0.3, 0.3], 95))
    assert mod.read(SimpleNamespace(host={"itl_gaps_s": []})) is None
