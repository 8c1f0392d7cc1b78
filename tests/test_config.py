"""Config system tests: precedence (Property 26, design.md:836-840),
validation (Property 27, design.md:842-846), and hot-reload
(requirements.md:146)."""

from __future__ import annotations

import pytest

from distributed_inference_server_tpu.core.errors import ConfigError
from distributed_inference_server_tpu.serving.config import (
    ConfigWatcher,
    ServerConfig,
)
from distributed_inference_server_tpu.serving.scheduler import SchedulingStrategy


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


class TestPrecedence:
    def test_defaults(self):
        cfg = ServerConfig.load()
        assert cfg.get("server", "port") == 8000
        assert cfg.get("queue", "high_watermark") == 1000
        assert cfg.get("batcher", "window_ms") == 50.0
        assert cfg.get("batcher", "max_batch_size") == 32

    def test_file_overrides_defaults_toml(self, tmp_path):
        path = _write(
            tmp_path, "c.toml",
            "[server]\nport = 9100\n[queue]\nhigh_watermark = 1500\n",
        )
        cfg = ServerConfig.load(file_path=path)
        assert cfg.get("server", "port") == 9100
        assert cfg.get("queue", "high_watermark") == 1500
        assert cfg.get("queue", "low_watermark") == 500  # untouched default

    def test_file_overrides_defaults_yaml(self, tmp_path):
        path = _write(tmp_path, "c.yaml", "server:\n  port: 9200\n")
        cfg = ServerConfig.load(file_path=path)
        assert cfg.get("server", "port") == 9200

    def test_env_overrides_file(self, tmp_path):
        path = _write(tmp_path, "c.toml", "[server]\nport = 9100\n")
        cfg = ServerConfig.load(
            file_path=path, environ={"DIS_TPU_SERVER__PORT": "9300"}
        )
        assert cfg.get("server", "port") == 9300

    def test_cli_overrides_env_and_file(self, tmp_path):
        """Property 26: CLI > env > file."""
        path = _write(tmp_path, "c.toml", "[server]\nport = 9100\n")
        cfg = ServerConfig.load(
            file_path=path,
            environ={"DIS_TPU_SERVER__PORT": "9300"},
            cli_args=["--server-port", "9400"],
        )
        assert cfg.get("server", "port") == 9400

    def test_cli_config_file_flag(self, tmp_path):
        path = _write(tmp_path, "c.toml", "[server]\nport = 9500\n")
        cfg = ServerConfig.load(cli_args=["--config", path])
        assert cfg.get("server", "port") == 9500
        assert cfg.source_file == path

    def test_env_type_coercion(self):
        cfg = ServerConfig.load(
            environ={
                "DIS_TPU_SERVER__AUTO_RESTART": "false",
                "DIS_TPU_BATCHER__WINDOW_MS": "75.5",
                "DIS_TPU_ENGINE__PREFILL_BUCKETS": "16,64,256",
            }
        )
        assert cfg.get("server", "auto_restart") is False
        assert cfg.get("batcher", "window_ms") == 75.5
        assert cfg.get("engine", "prefill_buckets") == [16, 64, 256]

    def test_unknown_key_rejected(self, tmp_path):
        path = _write(tmp_path, "c.toml", "[server]\nbogus = 1\n")
        with pytest.raises(ConfigError):
            ServerConfig.load(file_path=path)

    def test_typed_views(self):
        cfg = ServerConfig.load()
        assert cfg.queue_config().high_watermark == 1000
        assert cfg.batcher_config().max_batch_size == 32
        assert cfg.validator_config().max_context_tokens == 8192
        assert cfg.strategy() is SchedulingStrategy.LEAST_LOADED


class TestValidation:
    """Property 27: invalid values rejected (the CLI maps this to a
    non-zero exit)."""

    @pytest.mark.parametrize(
        "environ",
        [
            {"DIS_TPU_SERVER__PORT": "0"},
            {"DIS_TPU_SERVER__PORT": "99999"},
            {"DIS_TPU_SERVER__PORT": "not-a-number"},
            {"DIS_TPU_QUEUE__HIGH_WATERMARK": "-5"},
            {"DIS_TPU_QUEUE__LOW_WATERMARK": "2000"},  # >= high
            {"DIS_TPU_QUEUE__HIGH_WATERMARK": "5000"},  # > max_queue_size
            {"DIS_TPU_SERVER__STRATEGY": "psychic"},
            {"DIS_TPU_MODEL__DTYPE": "int4"},
            {"DIS_TPU_ENGINE__MAX_BATCH": "0"},
            # mixed step: negative, and width not exceeding max_batch
            {"DIS_TPU_ENGINE__MIXED_STEP_TOKENS": "-1"},
            {"DIS_TPU_ENGINE__MIXED_STEP_TOKENS": "64"},  # == max_batch
        ],
    )
    def test_invalid_rejected(self, environ):
        with pytest.raises(ConfigError):
            ServerConfig.load(environ=environ)

    def test_mixed_step_tokens_valid_and_off(self):
        cfg = ServerConfig.load(
            environ={"DIS_TPU_ENGINE__MIXED_STEP_TOKENS": "128"}
        )
        assert cfg.get("engine", "mixed_step_tokens") == 128
        assert ServerConfig.load().get("engine", "mixed_step_tokens") == 0

    def test_cli_exit_nonzero_on_invalid(self):
        from distributed_inference_server_tpu.__main__ import main

        assert main(["--server-port", "0"]) != 0

    def test_cli_refuses_an_unrequested_cpu_backend(self, capsys):
        """jax falls back to the CPU silently when it finds no
        accelerator; unless the CPU was asked for, main() must say so and
        exit non-zero instead of serving at CPU speed."""
        import jax

        from distributed_inference_server_tpu.__main__ import main

        assert jax.devices()[0].platform == "cpu"
        requested = jax.config.jax_platforms
        jax.config.update("jax_platforms", "")  # as if nothing was asked
        try:
            assert main([]) != 0
        finally:
            jax.config.update("jax_platforms", requested)
        assert "no accelerator" in capsys.readouterr().err


class TestCompileCache:
    """One policy for every entry point (utils/compile_cache.py): the
    directory is placed from outside, else it is the checkout's."""

    @pytest.fixture()
    def updates(self, monkeypatch):
        import jax

        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_env_variable_wins_and_no_directory_is_set_in_code(
            self, monkeypatch, updates, tmp_path):
        from distributed_inference_server_tpu.utils.compile_cache import (
            setup_compile_cache,
        )

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert setup_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

    def test_default_is_the_checkout(self, monkeypatch, updates):
        import os

        from distributed_inference_server_tpu.utils.compile_cache import (
            setup_compile_cache,
        )

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert setup_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want


class TestHotReload:
    def test_hot_diff_only_reloadable_keys(self):
        a = ServerConfig.load()
        b = ServerConfig.load(
            environ={
                "DIS_TPU_BATCHER__MAX_BATCH_SIZE": "16",
                "DIS_TPU_SERVER__PORT": "9999",  # not hot-reloadable
            }
        )
        diff = a.hot_diff(b)
        assert diff == {("batcher", "max_batch_size"): 16}

    def test_watcher_applies_file_change(self, tmp_path):
        path = _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 32\n")
        cfg = ServerConfig.load(file_path=path)
        watcher = ConfigWatcher(cfg)
        seen = []
        watcher.subscribe(lambda diff, new: seen.append(diff))

        import os

        _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 8\n")
        os.utime(path, (0, 0))  # force mtime change regardless of clock
        assert watcher.check_once() is True
        assert seen == [{("batcher", "max_batch_size"): 8}]
        assert watcher.current.get("batcher", "max_batch_size") == 8

    def test_reload_preserves_cli_overrides(self, tmp_path):
        """Property 26 must survive hot-reload: a file edit does not revert
        CLI-set keys, and passing --config inside cli_args is handled."""
        path = _write(tmp_path, "c.toml", "[queue]\nrequest_timeout_s = 10.0\n")
        cfg = ServerConfig.load(
            cli_args=["--config", path, "--batcher-window-ms", "10"]
        )
        assert cfg.get("batcher", "window_ms") == 10.0
        watcher = ConfigWatcher(cfg)

        import os

        _write(tmp_path, "c.toml", "[queue]\nrequest_timeout_s = 20.0\n")
        os.utime(path, (0, 0))
        assert watcher.check_once() is True
        # file change applied, CLI override NOT reverted
        assert watcher.current.get("queue", "request_timeout_s") == 20.0
        assert watcher.current.get("batcher", "window_ms") == 10.0

    def test_watcher_rejects_invalid_new_config(self, tmp_path):
        path = _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 32\n")
        cfg = ServerConfig.load(file_path=path)
        watcher = ConfigWatcher(cfg)

        import os

        _write(tmp_path, "c.toml", "[queue]\nhigh_watermark = -1\n")
        os.utime(path, (0, 0))
        assert watcher.check_once() is False
        assert watcher.current.get("batcher", "max_batch_size") == 32

    def test_server_applies_hot_config(self):
        """InferenceServer.apply_hot_config swaps live configs."""
        from distributed_inference_server_tpu.serving.server import InferenceServer

        srv = InferenceServer.__new__(InferenceServer)  # no engines needed
        from distributed_inference_server_tpu.serving.dispatcher import Dispatcher
        from distributed_inference_server_tpu.serving.scheduler import (
            AdaptiveScheduler,
        )

        srv.scheduler = AdaptiveScheduler(SchedulingStrategy.ROUND_ROBIN)
        srv.dispatcher = Dispatcher(srv.scheduler)
        new = ServerConfig.load(
            environ={
                "DIS_TPU_BATCHER__MAX_BATCH_SIZE": "4",
                "DIS_TPU_QUEUE__HIGH_WATERMARK": "50",
                "DIS_TPU_QUEUE__LOW_WATERMARK": "10",
                "DIS_TPU_SERVER__STRATEGY": "memory_aware",
            }
        )
        diff = ServerConfig.load().hot_diff(new)
        srv.apply_hot_config(diff, new)
        assert srv.dispatcher.batcher.config.max_batch_size == 4
        assert srv.dispatcher.queue.config.high_watermark == 50
        assert srv.scheduler.strategy() is SchedulingStrategy.MEMORY_AWARE

    def test_non_hot_keys_do_not_leak_through_hot_apply(self):
        """A non-hot-reloadable key (queue.max_queue_size) changing alongside
        a hot key must not be applied to the live queue config."""
        from distributed_inference_server_tpu.serving.dispatcher import Dispatcher
        from distributed_inference_server_tpu.serving.scheduler import (
            AdaptiveScheduler,
        )
        from distributed_inference_server_tpu.serving.server import InferenceServer

        srv = InferenceServer.__new__(InferenceServer)
        srv.scheduler = AdaptiveScheduler()
        srv.dispatcher = Dispatcher(srv.scheduler)
        old_cap = srv.dispatcher.queue.config.max_queue_size
        new = ServerConfig.load(
            environ={
                "DIS_TPU_QUEUE__REQUEST_TIMEOUT_S": "5",
                "DIS_TPU_QUEUE__MAX_QUEUE_SIZE": "5000",
            }
        )
        diff = ServerConfig.load().hot_diff(new)
        srv.apply_hot_config(diff, new)
        assert srv.dispatcher.queue.config.request_timeout_s == 5
        assert srv.dispatcher.queue.config.max_queue_size == old_cap


class TestWatcherFailureModes:
    """Hot-reload watcher robustness (VERDICT r2 weak #7): atomic
    replace, parse errors mid-write, and the brief-ENOENT window of a
    rename-based writer."""

    def test_torn_write_then_same_mtime_completion_still_reloads(
        self, tmp_path
    ):
        """A parse failure must NOT advance the recorded mtime: if the
        writer completes within the same filesystem-timestamp tick, the
        completed file would otherwise be treated as already-seen and
        never reload."""
        import os

        path = _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 32\n")
        watcher = ConfigWatcher(ServerConfig.load(file_path=path))

        _write(tmp_path, "c.toml", "[batcher\nmax_batch")  # torn write
        os.utime(path, (5, 5))
        assert watcher.check_once() is False  # old config stays active
        assert watcher.current.get("batcher", "max_batch_size") == 32

        _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 8\n")
        os.utime(path, (5, 5))  # SAME mtime as the torn snapshot
        assert watcher.check_once() is True
        assert watcher.current.get("batcher", "max_batch_size") == 8

    def test_atomic_replace_applies(self, tmp_path):
        """os.replace (the atomic-writer idiom) is picked up like an
        in-place edit."""
        import os

        path = _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 32\n")
        watcher = ConfigWatcher(ServerConfig.load(file_path=path))
        tmp = _write(tmp_path, "c.toml.tmp",
                     "[batcher]\nmax_batch_size = 4\n")
        os.replace(tmp, path)
        os.utime(path, (9, 9))
        assert watcher.check_once() is True
        assert watcher.current.get("batcher", "max_batch_size") == 4

    def test_enoent_window_survives_and_recovers(self, tmp_path):
        """The file briefly missing (between a writer's unlink and its
        rename) must not kill the watcher; the reload lands once the
        file is back."""
        import os

        path = _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 32\n")
        watcher = ConfigWatcher(ServerConfig.load(file_path=path))
        os.unlink(path)
        assert watcher.check_once() is False  # ENOENT: old config active
        assert watcher.current.get("batcher", "max_batch_size") == 32
        _write(tmp_path, "c.toml", "[batcher]\nmax_batch_size = 16\n")
        os.utime(path, (7, 7))
        assert watcher.check_once() is True
        assert watcher.current.get("batcher", "max_batch_size") == 16
