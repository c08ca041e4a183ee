"""What of ``chip_smoke.py`` the CPU can check: the shape of its last line,
that it cannot pass without a chip, and where the compile cache goes.
The run itself needs the chip (README, "Running")."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deeplearning4j_tpu.utils import environment  # noqa: E402


def test_last_line_has_the_contract_keys_and_no_others():
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = json.loads(json.dumps(chip_smoke.last_line(dev, 1)))
    assert set(line) == {"ok", "device"}
    assert set(line["device"]) == {"platform", "kind", "count"}
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.parametrize("argv", [
    # no chip: refused before any work
    ["--batch", "2", "--image", "32"],
    # the rehearsal walks the phases; one that raises ends the run
    ["--rehearse", "--batch", "0", "--image", "8"],
    # the four-chip path on a machine without four chips
    ["--chips", "4", "--rehearse", "--batch", "4", "--image", "32"],
], ids=["no-chip", "phase-raises", "no-four-chips"])
def test_off_the_chip_it_fails_and_prints_no_result(argv, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)    # not the checkout
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_cache_stays_where_the_environment_put_it(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert environment.place_jax_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_one_path_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = environment.place_jax_compile_cache()
        second = environment.place_jax_compile_cache()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:    # the suite must not fill the checkout
        jax.config.update("jax_compilation_cache_dir", before)
