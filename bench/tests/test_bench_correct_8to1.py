"""What decides ``correct``, on the CPU at DeepSeek LLM's layout: 8
query heads to each KV head, rotary on the whole head and no QKV bias
(``tiny-gqa-8to1.json``, toy widths, float32). The sound run serves
paged chunked prefill and decode through the pool, and its logits are
compared with the float32 full forward of ``dense_gqa``; the control
(that reference one precision lower) reads past the limits. The planted
faults stay on ``tiny-gqa`` (``test_bench_correct.py``)."""
import json
import time

import pytest

import test_bench_correct as base
from harness import cellrun, spec

CONFIG = "tiny-gqa-8to1"
MIX = {"app": "advanced_rag", "loop": "open", "rate_qps": 1.0,
       "ramp_s": 2, "tail_s": 30, "docs_per_query": 10,
       "question_words": {"mean": 17.61, "sigma": 0.4},
       "doc_words": {"mean": 46.3, "sigma": 0.5},
       "context_blocks": [4, 16]}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mix")
    (tmp / "tiny.json").write_text(json.dumps(MIX))
    s = json.loads((base.ROOT / "BENCHMARK.json").read_text())
    s["configs"].append({"name": CONFIG,
                         "file": f"bench/tests/{CONFIG}.json"})
    s["workloads"] = [{"name": f"{CONFIG}.tiny", "config": CONFIG,
                       "traffic": "tiny", "chips": 1}]
    for m in s["end_to_end"] + s["per_layer"]:
        m.pop("workloads", None)
    return spec.resolve(f"{CONFIG}.tiny", s, traffic_dir=tmp)


@pytest.fixture(scope="module")
def stack(cell):
    st = cellrun.setup(cell, base.SEED, time.time())
    yield st
    st.teardown()


def test_sound_run_is_correct_and_the_control_is_not(cell, stack):
    base.test_sound_run_is_correct_and_the_control_is_not(cell, stack)
