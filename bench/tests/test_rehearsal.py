"""CPU rehearsal of both drivers at a reduced configuration, through the
harness, with the chip check skipped (``bench/tests/tiny.py``). Device
metrics are never read here: a CPU run prints rates of the CPU backend,
which this file checks only for their arithmetic.

    python3 -m pytest bench/tests -q
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TINY = Path(__file__).with_name("tiny.py")
SEED = 2**31 + 12345          # larger than 32 bits hold, as a check's seeds are


def tiny(kind, *args, seed=SEED):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if kind == "train":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, str(TINY), kind, str(seed), *args],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out


def result(out) -> dict:
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_serve_counts_and_metrics():
    out = tiny("serve")
    r = result(out)
    assert r["correct"] is True
    assert r["failed"] == 0
    # whole batches of 8 slots were admitted
    assert r["attempted"] > 0 and r["attempted"] % 8 == 0
    m = r["metrics"]
    assert set(m) == {"output_tok_s", "ttft_p95_ms", "itl_p95_ms",
                      "setup_s"}
    # the rate is over the whole 2 s window: at most every admitted
    # request's 16 tokens
    assert 0 < m["output_tok_s"]["value"] <= r["attempted"] * 16 / 2.0
    # the first token waits for 8 prefill steps, a gap for one step
    assert m["ttft_p95_ms"]["value"] > m["itl_p95_ms"]["value"] > 0
    assert "[bench] in the window: 0 executables built" in out.stdout
    assert list(r)[-1] == "checks"
    assert out.stderr.strip().splitlines()[-1].startswith(
        "[check] served_gap_mean")


def test_train_counts_and_metrics():
    r = result(tiny("train"))
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == 4
    tok = r["metrics"]["train_tok_s"]["value"]
    # a rate over the whole window: whole steps of 16 x 32 tokens
    assert tok > 0 and (tok * 2.0) % (16 * 32) == 0
    assert set(r["checks"]) == {"loss_gap", "first_grad_gap", "update_gap"}


@pytest.mark.parametrize("fault", ["state_unchanged.serve", "token_altered"])
def test_serve_fault_is_not_correct(fault):
    assert result(tiny("serve", "--fault", fault))["correct"] is False


@pytest.mark.parametrize("fault", ["state_unchanged.train", "half_batch",
                                   "no_exchange"])
def test_train_fault_is_not_correct(fault):
    assert result(tiny("train", "--fault", fault))["correct"] is False


def test_serve_control_fails_its_limit():
    """The reference in float8 in the program's place reads above the
    limit the program stays under."""
    rows = [json.loads(line) for line in
            tiny("serve", "--calibrate").stdout.splitlines()
            if line.startswith("{")]
    (row,) = rows
    from tiny import SERVE_LIMITS
    assert row["program"]["mean"] <= SERVE_LIMITS["served_gap_mean"]
    assert row["control"]["mean"] > SERVE_LIMITS["served_gap_mean"]


def test_train_control_and_faults_fail_a_limit():
    rows = {r["reading"]: r for r in
            (json.loads(line) for line in
             tiny("train", "--calibrate").stdout.splitlines()
             if line.startswith("{"))}
    from tiny import TRAIN_LIMITS
    assert all(rows["program"][k] <= v for k, v in TRAIN_LIMITS.items())
    for name in ("control", "half_batch", "no_exchange"):
        assert any(rows[name][k] > v for k, v in TRAIN_LIMITS.items()), name


def test_no_result_without_a_chip():
    """Run as a check runs it, on a machine with no TPU: a non-zero exit
    and no result line."""
    root = Path(__file__).resolve().parents[2]
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-moe.b128-ctx256", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
