"""chip_smoke.py's contract with the driver, as far as the CPU can hold
it: the exact shape of the last line, refusal to run without a TPU, and
where the compile cache goes. The run itself happens on the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _assert_final_shape(doc):
    assert set(doc) == {"ok", "device"}
    assert set(doc["device"]) == {"platform", "kind", "count"}


@pytest.mark.parametrize("ok,device", [
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}),
    (False, {"platform": "cpu", "kind": "cpu", "count": 8,
             "extra": "must not leak"}),
    (False, {}),                      # JAX itself failed to start
])
def test_final_line_has_exactly_the_drivers_keys(ok, device):
    import chip_smoke

    doc = chip_smoke.final_line(ok, device)
    _assert_final_shape(doc)
    assert doc["ok"] is ok
    # What the driver parses is what json.dumps writes.
    _assert_final_shape(json.loads(json.dumps(doc)))


def test_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """Under JAX_PLATFORMS=cpu the script exits non-zero within
    seconds, runs no phase (no scratch data, no server, no native
    build), and still ends stdout with the two-key object, ok false."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    _assert_final_shape(last)
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    phases = [json.loads(line)["phase"] for line in lines[:-1]]
    assert phases == ["failed"]
    assert os.listdir(tmp_path) == []


@pytest.fixture()
def cache_config():
    """Put jax's cache directory back after the helper wrote it."""
    import jax

    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(monkeypatch, cache_config):
    from learningorchestra_tpu.parallel import distributed

    before = cache_config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert distributed.place_compile_cache() is None
    assert cache_config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  cache_config):
    from learningorchestra_tpu.parallel import distributed

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert distributed.place_compile_cache() == want
    assert cache_config.jax_compilation_cache_dir == want
