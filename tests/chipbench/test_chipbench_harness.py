"""A whole run of the harness on the CPU at a tiny size, with the look
for a chip skipped: the window, the comparison with the reference, and
``correct`` coming out false when the timed path is broken underneath.
Also: off a TPU the command exits non-zero and prints no result."""
from __future__ import annotations

import pathlib

import pytest

from chipbench import files, run

DATA = pathlib.Path(__file__).resolve().parent / "data"
E2E = [{"name": n, "unit": u} for n, u in (
    ("output_tok_s", "tokens/s"), ("ttft_p85_ms", "ms"),
    ("itl_p50_ms", "ms"), ("itl_p95_ms", "ms"), ("setup_s", "s"))]
E2E[1]["workloads"] = ["tiny.tiny"]           # no TTFT in the sessions cell
CELL = {"name": "tiny.tiny", "config": "tiny", "traffic": "tiny", "chips": 1}


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the tiny runs out of the persistent compilation cache."""
    monkeypatch.setattr(run, "place_compile_cache", lambda: "(off)")


def tiny_run(seed=5, seconds=2.0, traffic="tiny", control=False):
    return run.run_cell(f"tiny.{traffic}", CELL, files.config("tiny", DATA),
                        files.traffic(traffic, DATA), seed, seconds, False,
                        {"end_to_end": E2E, "per_layer": []},
                        require_tpu=False, control=control)


@pytest.mark.parametrize("traffic", ["tiny", "tiny-sessions"])
def test_tiny_run_is_correct_and_reports_every_metric(no_cache, traffic):
    res = tiny_run(traffic=traffic)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {e["name"] for e in E2E}
    if traffic == "tiny-sessions":
        want.discard("ttft_p85_ms")
        assert res["attempted"] > 4    # clients started new sessions
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_gap"]["limit"] == 0.05
    assert res["checks"]["tokens_compared"]["value"] > 0


def test_traced_run_reads_per_layer_metrics_after_the_window(no_cache):
    """The profiler traces the window's last seconds and stops after it
    has closed; the per-layer metrics that the host counts give are read
    (the CPU has no TPU plane, so the device's readers find nothing)."""
    bench = files.benchmark()
    res = run.run_cell("tiny.tiny", CELL, files.config("tiny", DATA),
                       files.traffic("tiny", DATA), 5, 2.0, True,
                       {"end_to_end": E2E, "per_layer": bench["per_layer"]},
                       require_tpu=False)
    assert res["correct"] is True
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["decode_batch_mean"]["value"] >= 1
    assert "decode_step_ms" not in res["metrics"]
    assert set(res["window"]["profiler_ms"]) == {"start", "stop"}
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def _break_step(monkeypatch, fault):
    from repro.serve.engine import Engine
    build = Engine._build_step

    def broken(self):
        step = build(self)

        def step_fn(params, caches, *rest):
            out = step(params, caches, *rest)
            if fault == "token":       # a token altered where it is made
                vocab = self.cfg.vocab_size
                out = ((out[0] + 1) % vocab,) + out[1:]
            else:                      # the step returns its state unchanged
                out = out[:-1] + (caches,)
            return out
        return step_fn
    monkeypatch.setattr(Engine, "_build_step", broken)


@pytest.mark.parametrize("fault", ["token", "state_unchanged"])
def test_broken_timed_path_is_not_correct(no_cache, monkeypatch, fault):
    _break_step(monkeypatch, fault)
    res = tiny_run(seed=6)
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > \
        res["checks"]["widest_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_limit(no_cache, seed):
    """The control, the reference one step below bfloat16, put in the
    program's place in a whole run, comes out not correct: the harness's
    own comparison judges it against the configuration's limit."""
    res = tiny_run(seed=seed, control=True)
    assert res["correct"] is False
    c = res["checks"]
    assert c["widest_gap"]["value"] > c["widest_gap"]["limit"]
    assert c["program_widest_gap"]["value"] <= c["widest_gap"]["limit"]
    assert c["tokens_compared"]["value"] > 0


def test_off_a_tpu_it_exits_non_zero_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", files.benchmark()["workloads"][0]["name"],
                  "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert out.out == ""
    assert "platform cpu" in out.err
