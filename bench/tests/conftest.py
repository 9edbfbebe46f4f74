"""CPU rehearsal of the benchmark: small sketches, short windows, no chip."""
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

SMOKE_POOL = 1 << 16


# Open-loop variants of the committed mix, for the generator's poisson mode
# and the latency numbers (no committed cell uses them yet).
VARIANTS = {
    "alarms-poisson": ("alarms-sat", {"mode": "poisson", "rate": 20000.0, "cap": 65536}, 0),
    "reach-poisson": ("alarms-sat", {"mode": "poisson", "rate": 20000.0, "cap": 65536}, 64),
}


def smoke(config_name: str, mix_name: str):
    """A cell's configuration and mix, cut to a sketch and graph the CPU
    runs in seconds; every other parameter as committed."""
    config = json.loads((ROOT / "bench/configs" / f"{config_name}.json").read_text())
    base, arrivals, reach = VARIANTS.get(mix_name, (mix_name, None, None))
    mix = json.loads((ROOT / "bench/traffic" / f"{base}.json").read_text())
    if arrivals is not None:
        mix["arrivals"] = dict(arrivals)
        mix["queries"]["reach_pairs"] = reach
    config["sketch"].update(depth=3, width_rows=256, width_cols=256)
    config["graph"]["scale"] = 12
    mix["arrivals"]["cap"] = 4096
    return config, mix


@pytest.fixture
def run_smoke():
    from bench import harness

    def run(config_name, mix_name, seconds=1.5, seed=2**31 + 7, **kw):
        config, mix = smoke(config_name, mix_name)
        return harness.run_cell(
            config, mix, seed, seconds, False, allow_cpu=True,
            pool_edges=SMOKE_POOL, log=lambda m: None, **kw,
        )

    return run
