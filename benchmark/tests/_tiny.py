"""Cells cut to sizes a test run holds on the CPU, and a whole run of
one, driven past the harness's look for a chip."""
import contextlib
import io
import json

from benchmark import run

TINY = {
    "etcd-register.campaign": {
        "config": {"ops_per_key": 20, "keys_per_history": 3},
        "traffic": {"histories_per_call": 4, "pool_calls": 2,
                    "corrupt_histories": 4, "sample_histories": 8}},
    "cockroach-register.one-test": {
        "config": {"keys_per_history": 3, "ops_per_key": 30},
        "traffic": {"pool_calls": 6, "corrupt_histories": 3,
                    "sample_histories": 6}},
}
SEED = 2**31 + 11


def tiny_config(cell: str) -> dict:
    return {**run.load_cell(cell)["config"], **TINY[cell]["config"]}


def drive(cell: str, call=None, trace: int = 0) -> dict:
    """The result line of a run of the tiny cell on the CPU; ``call``
    stands in for the system under test."""
    c = run.load_cell(cell)
    config = tiny_config(cell)
    traffic = {**c["traffic"], **TINY[cell]["traffic"]}
    sut = run.module("systems", config["system"])
    args = run.parse_args(["--workload", cell, "--seed", str(SEED),
                           "--seconds", "0.5", "--trace", str(trace)])
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.measure(args, c, config, traffic, sut,
                         call or sut.make(config), run.device_identity())
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
