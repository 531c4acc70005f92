"""The comparison fails the control: the configuration's precision
replaced by the next one below (a bf16 pack served for an fp32 tenant
through the program's own path, int4 tables computed by the reference in
an int8 tenant's place).  The field is the configuration's 16 trees at
depth 8 over the published test rows, and every request of the window is
compared: at the tiny default size bf16 tables change no served answer.
"""
import pytest

from tiny import correct, run_tiny

CASES = {
    "pen-8x2.gate.open": dict(dataset={"n_test": 3498}, rate_per_s=6000,
                              check_sample=6000),
    "isolet-8x2.gate.bulk": dict(dataset={"n_test": 1559},
                                 check_sample=100000),
}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_sound_run_is_correct_and_control_is_not(tmp_path, workload):
    size = dict(CASES[workload], field={"n_trees": 16, "depth": 8})
    out = run_tiny(tmp_path, workload, **size)
    assert correct(out), out["checks"]
    ctl = run_tiny(tmp_path, workload, control=True, **size)
    offedge = {k: v for k, (v, _) in ctl["checks"].items()
               if k.startswith("offedge_")}
    assert offedge and all(v > 0 for v in offedge.values()), ctl["checks"]
