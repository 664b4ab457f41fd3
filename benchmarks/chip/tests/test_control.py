"""The control of the correctness check, at a size a test run holds: the
reference's recurrence in bfloat16 put in the program's place reads
above a limit, and the program on the same inputs reads within them."""
import pytest

import control


def _over(rec):
    return [k for k, c in rec["compared"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", ["dense-small.solve", "sparse-small.solve",
                                  "served-small.serve"])
def test_control_fails_and_program_passes(checkout, cell):
    recs = control.main(["--workload", cell, "--seeds", "1", "2", "3",
                         "--program"], root=checkout)
    for rec in recs:
        if rec["side"] == "control":
            assert _over(rec), rec
        else:
            assert not _over(rec), rec
