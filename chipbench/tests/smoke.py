"""A cell at smoke widths for runs of the harness on the CPU.

It takes the published configuration file of a cell and shrinks every
size, so the harness, the engine and the reference all run their real
code paths in seconds.  Pallas kernels run in interpret mode where
``interpret`` is set."""
from __future__ import annotations

import copy

from chipbench import spec

SMOKE_SIZES = {"num_hidden_layers": 2, "hidden_size": 64,
               "intermediate_size": 128, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}


def smoke_cell(workload: str = "qwen3-0.6b.code", *,
               interpret: bool = False) -> dict:
    cell = copy.deepcopy(spec.cell(workload))
    cell["config"]["hf_config"].update(SMOKE_SIZES)
    if interpret:
        cell["config"]["numerics"].update(force=True, interpret=True,
                                          min_dim=64)
    mix = cell["traffic"]
    mix.update(prompt_lengths=[128, 256], prompt_weights=[0.5, 0.5],
               output={"dist": "uniform", "min": 4, "max": 8},
               rate_per_s=20.0, preroll_s=0.5,
               engine={"max_slots": 3, "chunked_prefill": 64},
               check={"finished": 3, "resident": 2})
    return cell
