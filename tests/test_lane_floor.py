"""The two-lane floor of the batch axis: a lone trace (or stream) runs
beside one all-NOP filler lane, never as a batch of one, and no device
of a sharded dispatch holds fewer than two lanes. Its results equal,
field by field, the same trace run beside a second trace and through
the reference engine; the ``emu.dispatch`` span counts the widened
dispatches as ``floored``."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import emulator, smcprog, spans
from repro.core.emulator import (Trace, run, run_many, run_ref, run_ref_many,
                                 run_stream, run_stream_many)
from repro.core.timescale import JETSON_NANO

FIELDS = ("exec_cycles", "row_hits", "served", "dram_ticks",
          "smc_fpga_cycles")
PER_REQUEST = ("t_resp", "t_issue")


def mk_trace(seed, n):
    """Reads, writes and RowClone ops with short dependency chains."""
    rng = np.random.RandomState(seed)
    return Trace.of(kind=rng.randint(0, 4, n), bank=rng.randint(0, 16, n),
                    row=rng.randint(0, 4096, n), delta=rng.randint(0, 24, n),
                    dep=rng.randint(0, 3, n))


LONE = mk_trace(5, 50)
OTHER = mk_trace(6, 45)        # same 64-request bucket


def assert_same(a, b, n):
    for k in FIELDS:
        assert int(a[k]) == int(b[k]), k
    for k in PER_REQUEST:
        np.testing.assert_array_equal(np.asarray(a[k])[:n],
                                      np.asarray(b[k])[:n], err_msg=k)


@pytest.fixture
def recording(monkeypatch):
    """Spans record as inside a profiler session."""
    monkeypatch.setattr(spans, "profiler_active", lambda: True)
    spans.clear()
    yield
    spans.clear()


def dispatch_counts():
    return [r.counts for r in spans.records() if r.name == "emu.dispatch"]


@pytest.mark.parametrize("b,lanes", [(1, 2), (2, 2), (3, 4), (4, 4),
                                     (5, 8), (17, 32)])
def test_batch_bucket_floor(b, lanes):
    assert emulator._batch_bucket(b) == lanes


@pytest.mark.parametrize("mode", ["auto", "off", "force"])
def test_floored_on_one_device(mode):
    """On one device only a lone trace is widened, whatever the
    sharding mode; a lone group's executable is never sharded but under
    'force', as every other one."""
    old = emulator.set_sharding(mode)
    try:
        assert [emulator._floored(b) for b in (1, 2, 3, 4)] == [1, 0, 0, 0]
        assert emulator._shard_count(emulator._batch_bucket(1)) == \
            (1 if mode == "force" else 0)
    finally:
        emulator.set_sharding(old)


@pytest.mark.parametrize("entry", ["run", "run_many", "run_ref",
                                   "run_stream"])
@pytest.mark.parametrize("mode", ["ts", "nots"])
def test_lone_trace_bit_identical(entry, mode):
    """A batch of one (filler lane beside it) against the same trace
    beside a second one through the same entry point, and against the
    reference engine run alone."""
    n = LONE.n
    if entry == "run":
        lone = run(LONE, JETSON_NANO, mode)
        pair = run_many([LONE, OTHER], JETSON_NANO, mode)[0]
    elif entry == "run_many":
        lone = run_many([LONE], JETSON_NANO, mode)[0]
        pair = run_many([OTHER, LONE], JETSON_NANO, mode)[1]
    elif entry == "run_ref":
        lone = run_ref(LONE, JETSON_NANO, mode)
        pair = run_ref_many([LONE, OTHER], JETSON_NANO, mode)[0]
    else:
        lone = run_stream(LONE, JETSON_NANO, mode, chunk=32)
        pair = run_stream_many([LONE, OTHER], JETSON_NANO, mode,
                               chunk=32)[0]
    assert_same(lone, pair, n)
    assert_same(lone, run_ref(LONE, JETSON_NANO, mode), n)


def test_lone_filtered_and_policy_rows_bit_identical():
    """The filler rows of a stacked filter, its lane mask and a policy
    table repeat row 0 and are discarded: a lone point with a filter, and
    a lone program on the policy axis, read as in a batch of two."""
    words = np.random.RandomState(7).randint(0, 1 << 31, 64)
    bloom = (words.astype(np.uint32), 3, 2048)
    lone = run_many([LONE], JETSON_NANO, "ts", blooms=[bloom])[0]
    pair = run_many([LONE, OTHER], JETSON_NANO, "ts",
                    blooms=[bloom, bloom])[0]
    assert_same(lone, pair, LONE.n)
    assert_same(lone, run_ref(LONE, JETSON_NANO, "ts", bloom=bloom), LONE.n)
    prog = next(iter(smcprog.builtin_programs().values()))
    (alone,) = emulator.run_policies(LONE, JETSON_NANO, [prog])
    two = emulator.run_policies(LONE, JETSON_NANO, [prog, prog])
    assert_same(alone, two[1], LONE.n)
    assert_same(alone, run(LONE, JETSON_NANO.with_policy(prog), "ts"),
                LONE.n)


@pytest.mark.parametrize("path", ["batched", "stream"])
def test_dispatch_counts_floored(recording, path):
    if path == "batched":
        run_many([LONE], JETSON_NANO, "ts")
        lone = dispatch_counts()
        spans.clear()
        run_many([LONE, OTHER], JETSON_NANO, "ts")
    else:
        run_stream(LONE, JETSON_NANO, "ts", chunk=32)
        lone = dispatch_counts()
        spans.clear()
        run_stream_many([LONE, OTHER], JETSON_NANO, "ts", chunk=32)
    pair = dispatch_counts()
    assert lone and pair
    assert all(c["lanes"] == 2 and c["floored"] == 1 and c["shards"] == 1
               for c in lone)
    assert all(c["lanes"] == 2 and c["floored"] == 0 for c in pair)


def test_two_devices_keep_two_lanes_a_device(tmp_path):
    """Two virtual devices: a padded batch of two stays on one device
    (no 2 x 1-lane shards), four shard as 2 x 2, and a lone trace and a
    pair (both floored there) read as on one device."""
    child = tmp_path / "child.py"
    child.write_text(
        "import json, os\n"
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')\n"
        "    + ' --xla_force_host_platform_device_count=2')\n"
        "import jax\n"
        "import numpy as np\n"
        "from repro.core import emulator\n"
        "from repro.core.emulator import Trace, run_many\n"
        "from repro.core.timescale import JETSON_NANO\n"
        "assert jax.local_device_count() == 2\n"
        "def mk(seed, n):\n"
        "    rng = np.random.RandomState(seed)\n"
        "    return Trace.of(kind=rng.randint(0, 4, n),\n"
        "                    bank=rng.randint(0, 16, n),\n"
        "                    row=rng.randint(0, 4096, n),\n"
        "                    delta=rng.randint(0, 24, n),\n"
        "                    dep=rng.randint(0, 3, n))\n"
        "lone, other = mk(5, 50), mk(6, 45)\n"
        "out = run_many([lone], JETSON_NANO, 'ts')\n"
        "out += run_many([lone, other], JETSON_NANO, 'ts')[:1]\n"
        "print(json.dumps({\n"
        "  'shards': [emulator._shard_count(b) for b in (2, 4, 8)],\n"
        "  'floored': [emulator._floored(b) for b in (1, 2, 3)],\n"
        "  'exec': [int(r['exec_cycles']) for r in out],\n"
        "  'resp': [np.asarray(r['t_resp'])[:50].tolist() for r in out]}))\n")
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    p = subprocess.run([sys.executable, str(child)], env=env,
                       capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["shards"] == [0, 2, 2]
    assert got["floored"] == [1, 1, 0]
    here = run(LONE, JETSON_NANO, "ts")
    assert got["exec"] == [int(here["exec_cycles"])] * 2
    want = np.asarray(here["t_resp"])[:50].tolist()
    assert got["resp"] == [want, want]
