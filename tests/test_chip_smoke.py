"""chip_smoke.py rehearsed on the CPU at a tiny size (on-chip-measurement
guide §2.1): the same phases, paths and control flow the chip run takes —
examples/llm-server booted with its warm-up, unary / streamed / long /
repeated / burst requests over HTTP, the no-compile-after-warm-up and
prefix-hit checks, shutdown — on the debug preset with the kernels in
interpret mode. It yields counts; the chip run yields everything else.
"""

import json
import subprocess
import sys

import pytest

import chip_smoke


def test_serving_phase_at_tiny_size():
    out = chip_smoke.serve(chip_smoke.TINY, require_tpu=False)
    assert out["served_from"]["platform"] == "cpu"
    assert out["requests_sent"] == out["requests_answered"] > 0
    assert out["requests_failed"] == 0 and out["tokens_returned"] > 0
    assert out["compiled_after_warmup"] == 0
    assert out["prefix_cache_hit_pages"] >= 1
    # from the engine's step records, so no client's clock decides it
    assert out["burst"]["admissions_into_running_decode"] > 0
    # every program it served from was there after warm-up
    assert len(out["programs_served"]) == out["programs"]


@pytest.mark.slow  # two engine boots; rehearse before a four-chip call
def test_tp_phase_at_tiny_size_on_virtual_devices():
    out = chip_smoke.run_tp(chip_smoke.TINY, require_tpu=False)
    assert out["sharded_over"] == chip_smoke.TINY.tp
    assert out["first_step_logit_max_diff"] <= (
        chip_smoke.TP_LOGIT_RTOL * out["first_step_logit_max_abs"])
    assert out["tokens_compared_equal"] == [chip_smoke.TP_TOKENS] * 3


def test_without_a_tpu_it_fails_and_prints_no_result():
    """JAX_PLATFORMS=cpu hides whatever chip there is: the command must
    exit non-zero and never print its ok line."""
    run = subprocess.run([sys.executable, chip_smoke.__file__],
                         capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "Unknown backend tpu" in run.stderr
    for line in run.stdout.splitlines():
        assert "ok" not in json.loads(line)
