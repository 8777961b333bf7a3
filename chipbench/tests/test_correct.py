"""The comparison that decides ``correct``, driven through a whole run of
a cell at smoke widths on the CPU (kernels in interpret mode), past the
harness's look for a chip.

A sound run comes out correct.  The timed path broken underneath, once
for each fault a one-chip serving cell can have, comes out not correct;
so do the controls: the program on its own three-pass matmul path
(``tcec_bf16x3`` in place of the configuration's ``tcec_bf16x6``), and
the reference with a bfloat16 K/V cache put in the program's place."""
import copy

import jax
import pytest

from chipbench import check, faults, run, spec, weights
from chipbench.tests.smoke import smoke_cell

SEED = 2 ** 32 + 17
SECONDS = 2.0


def _run(cell, keep=None):
    return run.run_cell(cell, SEED, SECONDS, False, jax.devices(),
                        keep_sample=keep)


@pytest.fixture(scope="module")
def cell():
    return smoke_cell(interpret=True)


@pytest.fixture(scope="module")
def sound(cell):
    keep = []
    return _run(cell, keep), keep


def test_sound_run_is_correct(sound):
    res, _ = sound
    assert res["correct"], res["check"]
    assert res["check"]["kv_rel_err"]["value"] is not None
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(cell, fault):
    with faults.FAULTS[fault]():
        res = _run(cell)
    assert not res["correct"], res["check"]


def test_three_pass_program_is_not_correct(cell):
    """The splits are explicit, so three bf16 passes lose on the CPU what
    they lose on the chip."""
    low = copy.deepcopy(cell)
    low["config"]["numerics"]["policy"] = "tcec_bf16x3"
    res = _run(low)
    assert not res["correct"], res["check"]


def test_control_comes_out_not_correct(cell, sound):
    """The reference with a bfloat16 K/V cache in the program's place."""
    _, (sample, cfg) = sound
    config = cell["config"]
    ref = spec.load_module(spec.reference_path(config["reference"]), "ref")
    w = weights.hf_view(weights.make_params(cfg, SEED), cfg)
    got = check.compare(ref, w, config["hf_config"], sample,
                        control={"precision": "highest",
                                 "kv_dtype": "bfloat16"})
    limits = cell["limits"]["limits"]
    assert (got["kv_rel_err"] > limits["kv_rel_err"]
            or got["token_gap"] > limits["token_gap"]), got
