"""The control: the reference one precision below the program's, float8
for bfloat16, put in the program's place, must fail the output check
that the program passes, on every seed."""

import pytest
from conftest import TINY_LIMIT
from tinybench import run_tiny


@pytest.mark.parametrize("seed", [11, 2**31 + 5, 987654321])
def test_control_fails_where_the_program_passes(bench_copy, seed):
    out = run_tiny(bench_copy, "tiny.closed2", seed=seed, seconds=0.5,
                   control=True)
    assert out.check["program_widest_logit_gap"] <= TINY_LIMIT
    gap = out.line["checks"]["widest_logit_gap"]
    assert gap["value"] == out.check["widest_logit_gap"] > TINY_LIMIT
    assert gap["limit"] == TINY_LIMIT
    assert out.line["correct"] is False


def test_a_pair_that_no_cell_names_has_every_metric_and_no_limit():
    from chipbench import catalog
    from chipbench.control import adhoc_cell

    cell = adhoc_cell("yi-6b-8L", "mixed_closed4")
    bench = catalog.load_benchmark()
    assert cell.checks is None
    assert cell.end_to_end == bench["end_to_end"]
    assert cell.per_layer == bench["per_layer"]
    assert cell.traffic["arrivals"]["kind"] == "closed"
