"""Small copies of the benchmark's cells, run through the whole harness on
the CPU backend (the harness's look for a chip is skipped)."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# what each cell's configuration and traffic become at test size; the
# limits stay the cell's own
SMALL = {
    "lasso": ({"samples": 1000, "features": 96}, {"nodes": 4},
              {"eps": 0.05, "record_every": 10, "budget_rounds": 600}),
    "logistic_l2": ({"samples": 2000, "features": 128}, {"nodes": 8},
                    {"rounds": 200, "block_size": 50}),
}


def small_cell(workload: str, chips: int = 1, config: str | None = None
               ) -> dict:
    """``workload`` as BENCHMARK.json resolves it, cut to test size;
    ``config``: another configuration file for it."""
    import json

    from bench import harness

    r = copy.deepcopy(harness.resolve(harness.load_spec(ROOT), workload, ROOT))
    if config is not None:
        r["config"] = json.loads((ROOT / config).read_text())
    data, solver, traffic = SMALL[r["config"]["problem"]["name"]]
    r["config"]["data"].update(data)
    r["config"]["solver"].update(solver)
    r["traffic"].update(traffic)
    r["cell"]["chips"] = chips
    return r


def run_small(workload: str, *, seed: int = 11, seconds: float = 0.05,
              trace: bool = False, chips: int = 1,
              system: str = "program") -> dict:
    """The result object of one small run of ``workload``."""
    from bench import harness

    return harness.run_cell(workload, seed, seconds, trace, root=ROOT,
                            system=system, allow_cpu=True,
                            resolved=small_cell(workload, chips))


RECORDED_TRACE = Path(__file__).with_name("data") / "v5e_tiny.xplane.pb"


def load_recorded():
    """A trace recorded on one v5e: two small jitted programs run three
    times each inside ``bench.window``, with a 2.8 ms ``host.sleep`` span
    between them; ``bench.solve`` spans the first of each pair."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(RECORDED_TRACE))
