"""The readers of the chunked-prefill counters (``prefill_passes``,
``prefill_rows``, ``prefill_row_capped``) on recorded counter values:
each computes its ratio over the window, and reads nothing (None, no
error) where no chunk step landed or the program keeps no such
counters."""
import os
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from harness import spec  # noqa: E402
from harness.load import Window  # noqa: E402

OPEN = {"prefill_passes": 40, "prefill_rows": 90, "prefill_row_capped": 1}
CLOSE = {"prefill_passes": 240, "prefill_rows": 690,
         "prefill_row_capped": 4}


def _metric(name):
    return spec.load_module(spec.metric_path(name),
                            "t_" + name.replace(".", "_")).read


def _run(open_stats, close_stats):
    w = Window(t_open=100.0, t_close=151.0, open_stats=dict(open_stats),
               close_stats=dict(close_stats))
    return SimpleNamespace(window=w, window_queries=lambda: [])


def test_prefill_rows_are_chunks_per_chunk_step():
    read = _metric("llm.prefill_rows.poisson")
    assert read(_run(OPEN, CLOSE)) == pytest.approx(600 / 200)
    full = {**CLOSE, "prefill_rows": 90 + 16 * 200}
    assert read(_run(OPEN, full)) == pytest.approx(16.0)


def test_prefill_row_capped_is_a_share_of_chunk_steps():
    read = _metric("llm.prefill_row_capped.poisson")
    assert read(_run(OPEN, CLOSE)) == pytest.approx(100.0 * 3 / 200)
    assert read(_run(OPEN, {**CLOSE, "prefill_row_capped": 1})) == 0.0


@pytest.mark.parametrize("name", ["llm.prefill_rows.poisson",
                                  "llm.prefill_row_capped.poisson"])
def test_a_window_without_a_chunk_step_reads_nothing(name):
    assert _metric(name)(_run(OPEN, OPEN)) is None


@pytest.mark.parametrize("name", ["llm.prefill_rows.poisson",
                                  "llm.prefill_row_capped.poisson"])
def test_a_program_without_the_counters_reads_nothing(name):
    """What the parent commit's engine keeps: no chunk-step counters."""
    old = {"prefill_tokens": 10, "decode_tokens": 20, "calls": 3,
           "decode_iters": 2, "new_shapes": 0, "token_reads": 3}
    assert _metric(name)(_run(old, old)) is None
