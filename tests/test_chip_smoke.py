"""chip_smoke.py on the CPU: the phases at a tiny size, the refusal to
run off-TPU, failure propagation, and the compile-cache helper the
entry points share."""

import json
import os
import subprocess
import sys

import pytest

import jax

import chip_smoke
from ytk_mp4j_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phase_gbdt_tiny():
    """4099 rows do not divide by 8 shards (padding rows), and with 8
    CPU devices the one-device comparison branch runs too. On a CPU
    mesh the phase asserts that NO Mosaic custom call is in the step."""
    out = chip_smoke.phase_gbdt(n_rows=4099, n_features=6, n_bins=32,
                                depth=3, n_trees=3, predict_rows=512)
    assert out["devices_spanned"] == jax.device_count()
    assert out["mosaic_custom_call"] is False
    assert out["logloss_tree3"] < out["logloss_tree1"]
    assert out["one_device_dp_q99"] <= 1e-4


def test_phase_ffm_tiny():
    out = chip_smoke.phase_ffm(n_rows=256, n_features=500, n_fields=4,
                               k=4, max_nnz=4)
    assert len(out["losses"]) == 3
    assert out["losses"][-1] < out["losses"][0]


def test_phase_driver_tiny():
    out = chip_smoke.phase_driver(length=1000, n_keys=300)
    n = jax.device_count()
    assert out["ranks"] == n
    # each rank's key range half-overlaps the next one's
    assert out["map_union"] == 300 + (n - 1) * 150


def test_subprocess_refuses_cpu_and_names_it():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero exit,
    the platform found is named, and no result line is printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert r.stdout.strip() == ""


def _main_on_cpu(monkeypatch):
    """Let main() past its platform check, and keep the test session's
    compiles out of the checkout's cache directory."""
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "enable_compilation_cache",
                        lambda: "<unused in tests>")


def test_failing_phase_fails_main(monkeypatch, capsys):
    """No phase's failure is caught: it leaves main() as the exception
    (a non-zero exit under ``sys.exit(main())``), later phases do not
    run, and no JSON result is printed."""
    ran = []

    def boom():
        raise RuntimeError("phase failed")

    _main_on_cpu(monkeypatch)
    monkeypatch.setattr(chip_smoke, "PHASES",
                        (("boom", boom), ("after", lambda: ran.append(1))))
    with pytest.raises(RuntimeError, match="phase failed"):
        chip_smoke.main()
    assert ran == []
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_main_prints_contract_json(monkeypatch, capsys):
    """The last line holds exactly ``ok`` and ``device`` (the device as
    jax reports it, exactly platform / kind / count) — the driver refuses
    any other key. The summary, claim null, is the line before it."""
    _main_on_cpu(monkeypatch)
    monkeypatch.setattr(chip_smoke, "PHASES", ((
        "gbdt", lambda: {"block_until_ready": {
            "block_secs": 0.2, "fetch_secs": 0.001, "blocks": True}}),))
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    dev = jax.devices()[0]
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and list(summary)[-1] == "claim"
    assert summary["machine"]["block_until_ready_blocks"] is True
    assert summary["machine"]["scalar_round_trip_secs"] > 0


_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_compilation_cache_include_metadata_in_key",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    before = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_cache_helper_sets_nothing_when_variable_set(cache_config,
                                                     monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set jax reads it itself; no
    code sets a directory."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    compile_cache.enable_compilation_cache()
    assert [a[0] for a in calls] == [
        "jax_compilation_cache_include_metadata_in_key"]


def test_cache_helper_default_is_fixed_checkout_path(cache_config,
                                                     monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compilation_cache()
    second = compile_cache.enable_compilation_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first



def test_a_named_scope_is_part_of_the_cache_key(cache_config, monkeypatch,
                                                tmp_path):
    """The device trace is read by the program's scope names, so a
    program must not be read from an entry compiled under other names:
    two programs that differ in a ``jax.named_scope`` alone are two
    entries once the helper has run, and one entry by jax's default."""
    from jax.experimental.compilation_cache import compilation_cache
    import jax.numpy as jnp

    def program(scope):
        def f(x):
            with jax.named_scope(scope):
                return x * 2 + 1
        return jax.jit(f)

    def entries(where, with_helper):
        compilation_cache.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(where))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        if with_helper:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(where))
            assert compile_cache.enable_compilation_cache() == str(where)
        for scope in ("mp4j.one", "mp4j.other"):
            program(scope)(jnp.arange(7.0)).block_until_ready()
        return len([f for f in os.listdir(where) if f.startswith("jit_f-")
                    and f.endswith("-cache")])

    assert entries(tmp_path / "default", with_helper=False) == 1
    assert entries(tmp_path / "helper", with_helper=True) == 2
