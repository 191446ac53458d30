"""The chip smoke test's golden values, recomputed on the CPU.

``chip_smoke.py`` checks a few of the TPU's tRCD and policy-axis points
against ``chip_smoke_golden.json``; these tests recompute the same
points here and require the same values. So the chip is held to the
CPU, and not only to its own reference engine on the same chip.

After a deliberate change to simulated statistics, refresh the file
from the repository root with::

  PYTHONPATH=src:tests python -c "import json, test_golden as g; \\
    json.dump(g.cpu_golden(), open('chip_smoke_golden.json', 'w'), \\
    indent=1, sort_keys=True)"
"""
import importlib.util
import json
import os

import pytest

from repro.core import emulator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cpu_golden(part: str = "") -> dict:
    """The golden points computed in this process; ``part`` ("trcd" or
    "policy") computes only that half."""
    cs = _chip_smoke()
    names, trs, trcd, programs, ptr, pol = [], [], [], [], None, []
    if part in ("", "trcd"):
        t, _, names, trs = cs.trcd_setup(cs.GOLDEN_KERNELS)
        trcd = t.campaign(trs).run()
    if part in ("", "policy"):
        sysf, programs, ptr = cs.policy_setup()
        pol = emulator.run_policies(ptr, sysf, programs)
    return cs.golden_values(names, trs, trcd, programs, ptr, pol)


@pytest.mark.parametrize("part", ["trcd", "policy"])
def test_golden_values_match_cpu(part):
    with open(os.path.join(ROOT, "chip_smoke_golden.json")) as fh:
        want = {k: v for k, v in json.load(fh).items()
                if k.startswith(part + "/")}
    assert want, f"no {part} points in chip_smoke_golden.json"
    assert cpu_golden(part) == want
