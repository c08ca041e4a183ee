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


@pytest.mark.parametrize("case", ["variable_before_import",
                                  "variable_after_import", "config",
                                  "switched_off", "neither", "url"])
def test_one_answer_to_where_the_cache_is(case, monkeypatch, tmp_path,
                                          place_jax_cache):
    """``jax_compile_cache_status()`` is what W112, the warm-ahead gates
    and the flight recorder ask. It names what jax uses: jax's own config
    once jax is imported, the variable jax will read before that."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    place_jax_cache(None)
    status = environment.jax_compile_cache_status
    if case == "variable_before_import":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.delitem(sys.modules, "jax")
        assert status() == (str(tmp_path), True)
    elif case == "variable_after_import":   # jax never saw it
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert status() == (None, False)
    elif case == "config":
        # not there yet (JAX makes it at its first write), under a
        # directory that can be written; then under a plain file
        place_jax_cache(tmp_path / "made" / "later")
        assert status() == (str(tmp_path / "made" / "later"), True)
        (tmp_path / "file").write_text("x")
        place_jax_cache(tmp_path / "file" / "cache")
        assert status() == (str(tmp_path / "file" / "cache"), False)
    elif case == "switched_off":
        place_jax_cache(tmp_path)
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            assert status() == (None, False)
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
        assert status() == (str(tmp_path), True)
    elif case == "neither":
        assert status() == (None, False)
    else:       # a bucket cannot be asked from here: taken on trust
        place_jax_cache("gs://b/cache")
        assert status() == ("gs://b/cache", True)
    assert not os.path.exists(tmp_path / "made")    # asked, not touched
