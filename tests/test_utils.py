"""Unit tests for config/log/timer/dashboard (reference tier-1 analogue,
SURVEY §4: Test/unittests/)."""

import time

import pytest

from multiverso_tpu.utils import config, log
from multiverso_tpu.utils.dashboard import Dashboard, monitor
from multiverso_tpu.utils.timer import Timer


class TestConfig:
    def test_defaults(self):
        assert config.get_flag("ps_role") == "default"
        assert config.get_flag("sync") is False
        assert config.get_flag("updater_type") == "default"

    def test_set_flag_coercion(self):
        config.set_flag("sync", "true")
        assert config.get_flag("sync") is True
        config.set_flag("num_workers", "4")
        assert config.get_flag("num_workers") == 4
        with pytest.raises(config.FlagError):
            config.set_flag("sync", "maybe")
        with pytest.raises(config.FlagError):
            config.set_flag("no_such_flag", 1)

    def test_parse_cmd_flags_compacts_argv(self):
        rest = config.parse_cmd_flags(
            ["prog", "-sync=true", "positional", "-updater_type=adagrad",
             "-unknown_flag=1"])
        assert rest == ["prog", "positional", "-unknown_flag=1"]
        assert config.get_flag("sync") is True
        assert config.get_flag("updater_type") == "adagrad"

    def test_parse_config_file(self, tmp_path):
        p = tmp_path / "cfg"
        p.write_text("# comment\nupdater_type=sgd\ncustom_key=42\n\n")
        pairs = config.parse_config_file(str(p))
        assert pairs == {"updater_type": "sgd", "custom_key": "42"}
        assert config.get_flag("updater_type") == "sgd"

    def test_define_and_reset(self):
        config.define_int("test_only_flag", 7, "test")
        config.set_flag("test_only_flag", 9)
        assert config.get_flag("test_only_flag") == 9
        config.reset_flags()
        assert config.get_flag("test_only_flag") == 7


class TestLog:
    def test_check(self):
        log.check(True)
        with pytest.raises(log.FatalError):
            log.check(False, "boom")

    def test_check_notnull(self):
        assert log.check_notnull(5) == 5
        with pytest.raises(log.FatalError):
            log.check_notnull(None, "ptr")

    def test_levels(self, capsys):
        logger = log.Logger(level=log.LogLevel.ERROR, name="t")
        logger.info("hidden")
        logger.error("shown")
        captured = capsys.readouterr()
        assert "hidden" not in captured.out + captured.err
        assert "shown" in captured.err


class TestDashboard:
    def test_monitor_accumulates(self):
        with monitor("op"):
            time.sleep(0.01)
        with monitor("op"):
            pass
        mon = Dashboard.get("op")
        assert mon.count == 2
        assert mon.total_ms >= 10.0
        assert "op" in mon.info_string()

    def test_display(self, capsys):
        with monitor("x"):
            pass
        Dashboard.display()
        out = capsys.readouterr().out
        assert "Dashboard" in out and "[x]" in out

    def test_notes_in_display_and_reset(self, capsys):
        """Free-form notes (native-transport counters) print alongside
        the monitors and clear on reset."""
        Dashboard.note("ps[t].native_served", "adds = 7, applies = 7")
        Dashboard.display()
        out = capsys.readouterr().out
        assert "native_served] adds = 7" in out
        Dashboard.reset()
        Dashboard.display()
        assert "native_served" not in capsys.readouterr().out


def test_timer():
    t = Timer()
    time.sleep(0.005)
    assert t.elapse() >= 5.0
    t.start()
    assert t.elapse() < 5.0


def test_documentation_citations_resolve():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "tools" / "check_parity.py")
    spec = importlib.util.spec_from_file_location("check_parity", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check() == []


class TestCompileCache:
    """utils/platform.enable_compile_cache: whoever launches the process
    places the cache; otherwise it sits in the checkout, at one path."""

    def test_honours_a_placed_cache(self, monkeypatch, tmp_path):
        import jax

        from multiverso_tpu.utils.platform import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before   # untouched

    def test_default_is_one_path_from_any_directory(self, monkeypatch,
                                                    tmp_path):
        import os

        import jax

        from multiverso_tpu.utils import platform
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.chdir(tmp_path)
            first = platform.enable_compile_cache()
            monkeypatch.chdir("/")
            second = platform.enable_compile_cache()
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert first == second == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
