"""Config precedence env > yaml > default (parity: reference scheduler.py:46-66)."""

import pytest

from k8s_llm_scheduler_tpu.config import Config, load_config


class TestDefaults:
    def test_defaults_without_yaml_or_env(self):
        cfg = load_config(yaml_path=None, env={})
        assert cfg.get("scheduler.name") == "ai-llama-scheduler"
        assert cfg.get("llm.temperature") == 0.3
        assert cfg.get("llm.max_tokens") == 200
        assert cfg.get("cache.ttl_seconds") == 300
        assert cfg.get("circuit_breaker.failure_threshold") == 5

    def test_tpu_fields_present(self):
        """The north-star llm block additions: mesh/max_batch."""
        cfg = load_config(yaml_path=None, env={})
        assert cfg.get("llm.mesh") == {"dp": 1, "tp": 1}
        assert cfg.get("llm.max_batch") == 8

    def test_formerly_dead_keys_live(self):
        """Keys the reference declared but never read (SURVEY §5) are real here."""
        cfg = load_config(yaml_path=None, env={})
        assert cfg.get("scheduler.watch_interval") == 60
        assert cfg.get("llm.retry_delay") == 1.0
        assert cfg.get("metrics.port") == 9090
        assert cfg.get("circuit_breaker.half_open_max_calls") == 1


class TestPrefillLadder:
    """llm.prefill_buckets is stated four times; a wave's suffix prefill is
    compiled at the smallest entry that holds its longest suffix, so a
    ladder that lacks a step pads every wave to the next one (PERF.md §6,
    PR 33: config.py began at 256 and every 64-85-token wave ran at 256)."""

    @pytest.mark.parametrize("where", ["engine", "build_local_backend", "config.yaml"])
    def test_every_statement_of_the_ladder_is_config_pys(self, where):
        import inspect
        from pathlib import Path

        from k8s_llm_scheduler_tpu.config import DEFAULTS

        if where == "config.yaml":
            path = Path(__file__).resolve().parents[1] / "config.yaml"
            stated = load_config(yaml_path=path, env={}).get("llm.prefill_buckets")
        else:
            from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
            from k8s_llm_scheduler_tpu.engine.local import build_local_backend

            fn = InferenceEngine.__init__ if where == "engine" else build_local_backend
            stated = inspect.signature(fn).parameters["prefill_buckets"].default
        assert list(stated) == DEFAULTS["llm"]["prefill_buckets"]
        assert list(stated)[:2] == [128, 256]


class TestYamlLayer:
    def test_yaml_overrides_defaults(self, tmp_path):
        path = tmp_path / "config.yaml"
        path.write_text("llm:\n  temperature: 0.7\n  max_batch: 32\n")
        cfg = load_config(yaml_path=path, env={})
        assert cfg.get("llm.temperature") == 0.7
        assert cfg.get("llm.max_batch") == 32
        assert cfg.get("llm.max_tokens") == 200  # untouched default

    def test_yaml_deep_merge_preserves_siblings(self, tmp_path):
        path = tmp_path / "config.yaml"
        path.write_text("scheduler:\n  name: custom\n")
        cfg = load_config(yaml_path=path, env={})
        assert cfg.get("scheduler.name") == "custom"
        assert cfg.get("scheduler.watch_interval") == 60

    def test_bad_yaml_rejected(self, tmp_path):
        path = tmp_path / "config.yaml"
        path.write_text("- just\n- a\n- list\n")
        with pytest.raises(ValueError):
            load_config(yaml_path=path, env={})


class TestEnvLayer:
    def test_env_overrides_yaml(self, tmp_path):
        path = tmp_path / "config.yaml"
        path.write_text("scheduler:\n  name: from-yaml\n")
        cfg = load_config(yaml_path=path, env={"SCHEDULER_NAME": "from-env"})
        assert cfg.get("scheduler.name") == "from-env"

    def test_env_type_coercion(self):
        cfg = load_config(
            yaml_path=None,
            env={
                "LLM_TIMEOUT": "30",
                "CACHE_ENABLED": "false",
                "CACHE_TTL": "60",
                "METRICS_ENABLED": "true",
            },
        )
        assert cfg.get("llm.timeout") == 30
        assert cfg.get("cache.enabled") is False
        assert cfg.get("cache.ttl_seconds") == 60
        assert cfg.get("metrics.enabled") is True

    def test_reference_env_names_work(self):
        """The reference's env names (scheduler.py:56-60) keep working."""
        cfg = load_config(
            yaml_path=None,
            env={"LLM_MODEL": "llama-3.3-70b-instruct", "MAX_RETRIES": "5"},
        )
        assert cfg.get("llm.model") == "llama-3.3-70b-instruct"
        assert cfg.get("llm.max_retries") == 5


class TestAccess:
    def test_missing_key_raises(self):
        cfg = Config({"a": {"b": 1}})
        assert cfg.get("a.b") == 1
        assert cfg.get("a.z", 9) == 9
        with pytest.raises(KeyError):
            cfg.get("a.z")

    def test_section(self):
        cfg = load_config(yaml_path=None, env={})
        assert cfg.section("cache")["ttl_seconds"] == 300
        assert cfg.section("nope") == {}


class TestRobustness:
    def test_scalar_section_rejected(self, tmp_path):
        path = tmp_path / "config.yaml"
        path.write_text("scheduler: 5\n")
        with pytest.raises(ValueError, match="must be a mapping"):
            load_config(yaml_path=path, env={})

    def test_defaults_not_shared_across_loads(self):
        cfg1 = load_config(yaml_path=None, env={})
        cfg1.section("llm")["mesh"]["tp"] = 4
        cfg1.get("llm.prefill_buckets").append(999)
        cfg2 = load_config(yaml_path=None, env={})
        assert cfg2.get("llm.mesh") == {"dp": 1, "tp": 1}
        assert 999 not in cfg2.get("llm.prefill_buckets")

    def test_bad_env_value_names_variable(self):
        with pytest.raises(ValueError, match="LLM_TIMEOUT"):
            load_config(yaml_path=None, env={"LLM_TIMEOUT": "not-a-number"})
