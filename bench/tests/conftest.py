"""Shared fixtures: the benchmark's cells cut to a size the CPU runs.

``smoke_cell`` loads a cell of ``BENCHMARK.json`` as the harness does and
swaps its sizes for small ones: 2 layers, narrow widths, a small vocabulary
and short requests.  Everything else (engine arguments, traffic generator,
weight rule, reference, checks) is the cell's own.
"""

from __future__ import annotations

import copy
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SMOKE = {
    "phi3-mini-3.8b": {
        "top": {"num_hidden_layers": 2, "hidden_size": 256,
                "intermediate_size": 512, "num_attention_heads": 8,
                "num_key_value_heads": 4, "head_dim": 32, "vocab_size": 512},
        "program": {"num_layers": 2, "d_model": 256, "num_heads": 8,
                    "num_kv_heads": 4, "head_dim": 32, "d_ff": 512,
                    "vocab_size": 512, "max_seq_len": 256},
        "engine": {"capacity": 256, "num_blocks": 96},
    },
    "rwkv6-3b": {
        "top": {"num_hidden_layers": 2, "hidden_size": 256,
                "intermediate_size": 512, "vocab_size": 512},
        "program": {"num_layers": 2, "d_model": 256, "num_heads": 4,
                    "num_kv_heads": 4, "d_ff": 512, "vocab_size": 512},
        "engine": {"capacity": 256},
    },
}


#: cells of the tests: those of BENCHMARK.json, and the recurrent path's,
#: whose configuration waits on the program (PERF.md, Open questions)
CELLS = {
    "phi3-chat-det50": ("phi3-mini-3.8b", "chat-det50"),
    "phi3-chat-det0": ("phi3-mini-3.8b", "chat-det0"),
    "rwkv6-chat-det50": ("rwkv6-3b", "chat-det50"),
}


def smoke_cell(workload: str, prompt_max: int = 48, output_max: int = 24):
    from bench import spec

    config, traffic = CELLS[workload]
    cell = spec.make_cell(workload, spec.BENCH / "configs" / f"{config}.json",
                          traffic, 1, spec.load_json(spec.ROOT / "BENCHMARK.json"))
    cell = copy.deepcopy(cell)
    conf = cell.config
    cut = SMOKE[conf["name"]]
    conf.update(cut["top"])
    conf["program"].update(cut["program"])
    conf["engine"].update(cut["engine"])
    mix = cell.traffic
    mix["prompt"].update(min=4, max=prompt_max)
    mix["output"].update(min=4, max=output_max)
    return cell


@pytest.fixture
def smoke():
    return smoke_cell
