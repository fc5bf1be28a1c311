"""The reader of the fused-batch merge's fallback counter, on hand-built
runs."""
import pytest

from chipbench.harness import Run, load_module


def test_merge_fallback_reader():
    read = load_module("layer_metrics/merge_fallback.bulk.py").read
    counted = {"repro_slot_topk_batches_total": {
        "outcome=certified": 198.0, "outcome=fallback": 2.0}}
    assert read(Run(counters=counted)) == pytest.approx(1.0)
    certified = {"repro_slot_topk_batches_total": {"outcome=certified": 7.0}}
    assert read(Run(counters=certified)) == 0.0
    # no fused-batch batch in the window, obs off, or the parent program
    assert read(Run(counters={"repro_slot_topk_batches_total": {}})) is None
    assert read(Run(counters={"repro_serve_batches_total": {"": 3.0}})) \
        is None
    assert read(Run()) is None
