"""The three readers of the program's span counters, on hand-built runs:
each gives a value from the cells it names, and nothing, without raising,
from a program whose counters lack them (the parent of the PR that brought
the spans)."""
import pytest

from benchmark.harness import spec


def cell(count, seconds):
    return {"count": count, "items": 0, "seconds": seconds,
            "max_thread_seconds": seconds, "workers": 1, "bytes": 0}


LEGACY = {"stage": cell(10, 0.05), "transfer": cell(20, 0.04),
          "dispatch_wait": cell(10, 0.5)}
BEFORE = dict(LEGACY, **{"train.build": cell(1, 4.0), "train.init_state": cell(1, 2.5),
                         "train.step": cell(3, 0.9), "input.wait": cell(3, 0.3)})
AFTER = dict(LEGACY, **{"train.build": cell(1, 4.0), "train.init_state": cell(1, 2.5),
                        "train.step": cell(13, 0.95), "input.wait": cell(13, 0.42)})


@pytest.mark.parametrize("name,want", [
    ("input_wait_ms", 1e3 * (0.42 - 0.3) / 10),
    ("dispatch_ms", 1e3 * (0.95 - 0.9) / 10),
    ("trainer_init_s", 6.5),
])
def test_a_span_reader_reads_its_cells(name, want):
    read = spec.module("layer_metrics", name).read
    assert read({"stages_before": BEFORE, "stages_after": AFTER}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["input_wait_ms", "dispatch_ms", "trainer_init_s"])
def test_a_span_reader_finds_nothing_in_a_program_without_the_spans(name):
    read = spec.module("layer_metrics", name).read
    assert read({"stages_before": LEGACY, "stages_after": LEGACY}) is None
    assert read({"stages_before": {}, "stages_after": {}}) is None


@pytest.mark.parametrize("name", ["input_wait_ms", "dispatch_ms"])
def test_a_window_without_a_dispatch_reads_nothing(name):
    read = spec.module("layer_metrics", name).read
    assert read({"stages_before": BEFORE, "stages_after": BEFORE}) is None
