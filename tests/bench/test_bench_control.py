"""The lower-precision control: the program with its float tables in
bfloat16 (its own ``init_state(dtype=...)`` path) fails the comparison,
and the same tiny runs in float32 pass it."""

import pytest

from benchlib import failing, run_tiny


@pytest.mark.parametrize("config", ["tiny-merge", "tiny-escrow"])
def test_sound_run_is_correct(config):
    res = run_tiny(config)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("config", ["tiny-merge", "tiny-escrow"])
def test_bf16_control_fails(config):
    res = run_tiny(config, float_dtype="bfloat16")
    assert not res["correct"]
    assert "float_gap" in failing(res)
