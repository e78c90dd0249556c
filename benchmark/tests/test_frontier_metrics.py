"""The four-chip cell's readers: the frontier kernel's time (the mean
over chips), its exchange share and roofline share on a small trace in
the plain form, and the route share on a tiny traced run of the cell on
a four-device CPU mesh."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import frontier_work, generator, run, workmodel
from benchmark.devtrace import Trace
from benchmark.metrics import frontier_exchange_share as exchange
from benchmark.metrics import frontier_kernel_ms_per_khist as kernel
from benchmark.metrics import frontier_roofline as roofline

MS = 1_000_000
CELL = "etcd-register-pause.wide-x4"
#: The cell cut to what a test run holds: 40 ops per key, 8 of them
#: pinned :info (W up to 18), 2 histories a call; no reference work.
TINY = {"config": {"ops_per_key": 40, "info_share": 0.2},
        "traffic": {"histories_per_call": 2, "pool_calls": 2,
                    "corrupt_histories": 0, "sample_histories": 0}}


def planes():
    """Two chips, each running the frontier kernel 0-8 ms (chip 0) or
    0-6 ms (chip 1) inside one call of 0-10 ms, with an exchange of
    1 ms and a psum of 1 ms each, and one more kernel outside it."""
    def chip(kern_ms):
        ops = [("%while.3 = u32[64,65536] while(...)", 0, kern_ms * MS),
               ("%collective-permute-done.1 = u32[64,65536] cp(...)",
                1 * MS, 1 * MS),
               ("%all-reduce.2 = s32[] all-reduce(...)", 3 * MS, 1 * MS),
               ("%collective-permute-done.1 = u32[64,65536] cp(...)",
                12 * MS, 1 * MS)]
        mods = [("jit_frontier_check(3)", 0, kern_ms * MS),
                ("jit_frontier_check(3)", 12 * MS, 2 * MS),
                ("jit_check(4)", 9 * MS, 1 * MS)]
        return [{"name": "XLA Ops", "events": ops},
                {"name": "XLA Modules", "events": mods}]
    return [{"name": "/host:CPU", "lines": [
                {"name": "python", "events": [("bench.call", 0, 10 * MS)]}]},
            {"name": "/device:TPU:0", "lines": chip(8)},
            {"name": "/device:TPU:1", "lines": chip(6)}]


def fake_run(trace, n=2):
    cfg = dict(run.load_cell(CELL)["config"], **TINY["config"])
    pool = generator.generate(cfg, n, 2**31 + 3, 0)
    return run.Run(calls=[{"t0": 0.0, "t1": 0.01, "n": n,
                           "batch": list(range(n))}],
                   window_s=0.01, setup_s=1.0, pool=pool,
                   recorder=run.Recorder(), window_compiles=0, trace=trace,
                   device={"kind": "TPU v5 lite", "count": 2}, on_chip=True)


def test_kernel_time_is_the_mean_over_chips():
    r = fake_run(Trace(planes()))
    assert kernel.per_chip_s(r) == {"/device:TPU:0": pytest.approx(0.008),
                                    "/device:TPU:1": pytest.approx(0.006)}
    assert kernel.read(r) == pytest.approx(0.007 * 1e3 / 0.002)


def test_exchange_share_counts_collectives_inside_the_kernel():
    # 2 ms of collectives in each chip's kernel (the one at 12 ms lies
    # outside the window): 2/8 and 2/6.
    got = exchange.read(fake_run(Trace(planes())))
    assert got == pytest.approx(100.0 * (2 / 8 + 2 / 6) / 2)


def test_roofline_is_the_least_per_chip_time_over_the_kernel_time():
    r = fake_run(Trace(planes()))
    ops = nbytes = 0.0
    for i in range(r.pool.n):
        w = frontier_work.history_work(r.pool, i, 2)
        ops += w["ops"]
        nbytes += w["bytes"]
    t, bound = workmodel.least_time(
        ops, nbytes, roofline.peaks_for("TPU v5 lite"))
    got = roofline.read(r)
    assert got == {"value": pytest.approx(100.0 * t / 0.007),
                   "bound": bound}
    assert 0 < got["value"] <= 100


def test_frontier_work_by_hand():
    # write 1 then read 1 by one process: 2 events at peak 1, 2 states.
    typ, proc, f = [0, 1, 0, 1], [0, 0, 0, 0], [1, 1, 0, 0]
    a, b = [1, 1, -1, 1], [-1] * 4
    w = frontier_work.key_work(typ, proc, f, a, b, 4)
    local = 2 * 1 / 4                         # 2^1 masks x 1 word / 4
    assert w == {"ops": 2 * local, "bytes": 2 * 2 * local * 4}


def test_device_readers_read_nothing_without_the_frontier_kernel():
    for tr in (None, Trace([p for p in planes()
                            if p["name"] == "/host:CPU"])):
        r = fake_run(tr)
        assert kernel.read(r) is None
        assert exchange.read(r) is None
        assert roofline.read(r) is None


def test_route_share_reads_100_on_a_four_device_mesh():
    """A tiny traced run of the cell on four CPU devices: every row of
    W up to 18 rides the frontier route; the device-trace readers stay
    out of a line run off the chip."""
    code = f"""
import contextlib, io, json, sys
from benchmark import run
tiny = json.loads({json.dumps(json.dumps(TINY))})
c = run.load_cell({CELL!r})
config = dict(c["config"], **tiny["config"])
traffic = dict(c["traffic"], **tiny["traffic"])
sut = run.module("systems", config["system"])
args = run.parse_args(["--workload", {CELL!r}, "--seed", "2147483659",
                       "--seconds", "0.5", "--trace", "1"])
out = io.StringIO()
with contextlib.redirect_stdout(out):
    rc = run.measure(args, c, config, traffic, sut, sut.make(config),
                     run.device_identity())
assert rc == 0
print(out.getvalue().strip().splitlines()[-1])
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JT_COMPILE_CACHE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=run.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    m = line["metrics"]
    assert line["device"]["count"] == 4 and line["correct"] is True
    assert m["frontier_route_share"]["value"] == 100.0
    for name in ("frontier_kernel_ms_per_khist", "frontier_exchange_share",
                 "frontier_roofline"):
        assert name not in m
