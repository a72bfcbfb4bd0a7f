"""The whole-run tests of ``test_chipbench_harness.py`` on the second CPU
double, ``tiny-internlm2``: InternLM2's shape at a tiny size (GQA group
6, eps 1e-5, RoPE base 1e6, a vocabulary that is not a multiple of 256,
so the padded logits are masked).  The run comes out correct against
``chipbench/reference.py``, and the fp8 control comes out not correct.
The scaffolding (metric list, cell, cache fixture) is the harness
tests' own."""
from __future__ import annotations

import pytest

from chipbench import files, run
from test_chipbench_harness import CELL, DATA, E2E, no_cache  # noqa: F401

CONFIG = "tiny-internlm2"


def internlm2_run(seed=5, seconds=2.0, traffic="tiny", control=False):
    return run.run_cell(f"tiny.{traffic}", {**CELL, "config": CONFIG},
                        files.config(CONFIG, DATA),
                        files.traffic(traffic, DATA), seed, seconds, False,
                        {"end_to_end": E2E, "per_layer": []},
                        require_tpu=False, control=control)


def test_the_internlm2_double_has_its_shape():
    m = files.config(CONFIG, DATA)["model"]
    full = files.config("internvl2-26b-lm")["model"]
    assert m["num_heads"] // m["num_kv_heads"] == \
        full["num_heads"] // full["num_kv_heads"] == 6
    for k in ("rope_theta", "rms_norm_eps", "dtype"):
        assert m[k] == full[k]
    assert m["vocab_size"] % 256 and full["vocab_size"] % 256


@pytest.mark.parametrize("traffic", ["tiny", "tiny-sessions"])
def test_internlm2_run_is_correct_and_reports_every_metric(no_cache,
                                                           traffic):
    res = internlm2_run(traffic=traffic)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {e["name"] for e in E2E}
    if traffic == "tiny-sessions":
        want.discard("ttft_p85_ms")
        assert res["attempted"] > 4    # clients started new sessions
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["widest_gap"]["limit"] == 0.05
    assert res["checks"]["tokens_compared"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_internlm2_fp8_control_fails_the_limit(no_cache, seed):
    res = internlm2_run(seed=seed, control=True)
    assert res["correct"] is False
    c = res["checks"]
    assert c["widest_gap"]["value"] > c["widest_gap"]["limit"]
    assert c["program_widest_gap"]["value"] <= c["widest_gap"]["limit"]
    assert c["tokens_compared"]["value"] > 0
