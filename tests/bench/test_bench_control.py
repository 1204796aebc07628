"""The control: the plain reference at precision ``high`` (three bfloat16
passes, the nearest below the configurations' float32 at ``highest``) put
in the program's place must come out not correct."""
import pytest

from bench_small import run_small


@pytest.mark.parametrize("workload", ["lasso_cert", "eps_rounds"])
def test_control_is_not_correct(workload):
    out = run_small(workload, system="control")
    assert out["correct"] is False
    failed = [name for name, e in out["check"].items()
              if not e["value"] <= e["limit"]]
    assert failed, out["check"]


def test_reference_at_highest_passes_its_own_check(monkeypatch):
    """The same swap at the configurations' precision is correct, so the
    control fails by its precision alone."""
    from bench import systems
    from bench.reference import cola as ref

    real = ref.Instance

    class Highest(real):
        def __init__(self, a, y, solver, problem, precision):
            super().__init__(a, y, solver, problem, "highest")
    monkeypatch.setattr(systems.ref, "Instance", Highest)
    out = run_small("eps_rounds", system="control")
    assert out["correct"] is True, out["check"]
