"""chip_smoke.py on the CPU: it must never pass here, its body must drive
the real stack at `llm.model: tiny`, and the one door every entry point
uses must refuse a CPU nobody asked for."""

import json

import jax
import pytest

import chip_smoke
from k8s_llm_scheduler_tpu.engine.local import build_local_backend


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""  # no result line
    assert "no TPU" in err and "cpu" in err


def test_body_binds_every_pod_from_the_model_at_tiny():
    cfg = chip_smoke.smoke_config(model="tiny", bpe_fixture=False)
    summary = chip_smoke.run(cfg, nodes=3, pods=12, shapes=6)
    assert summary["failures"] == []
    assert summary["bound"] == 12
    assert summary["decisions_by_source"] == {"llm": 6, "cache": 6, "fallback": 0}
    assert summary["breaker"]["trips"] == 0
    assert summary["decode_driver"] == ["wave_block_decode"]
    assert summary["model"]["name"] == "tiny"
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": len(jax.devices())}
    # auto resolves to the einsum path off the chip, and says so: per call
    # site, how many traced geometries got which implementation
    assert set(summary["attention_impls"]) == {"prefix", "causal_chunk"}
    for by_impl in summary["attention_impls"].values():
        assert set(by_impl) == {"xla"} and min(by_impl.values()) >= 1
    assert all(line.endswith(": xla") for line in summary["attention_shapes"])
    assert summary["reference"]["ok"] and summary["reference"]["finite"]
    assert summary["compiled"]["setup"]["programs"] > 0
    assert summary["compiled"]["serve"]["programs_over_1s"] == 0
    json.dumps(summary, default=str)  # the line main() prints


def test_attention_record_adds_no_metric_name_per_geometry():
    """The resolved-implementation record rides get_stats into /metrics:
    its names come from the call sites and the implementations, never from
    the traced shapes."""
    from k8s_llm_scheduler_tpu.observability.metrics import _flatten
    from k8s_llm_scheduler_tpu.ops.attention import AttnImpl, _note_resolved

    impl = AttnImpl()
    for rows in (8, 4, 2, 1):
        _note_resolved(impl.resolved, "prefix", (rows, 256, 32, 64), 2048, False, None)
    _note_resolved(impl.resolved, "prefix", (8, 24, 32, 64), 2048, True, None)
    assert impl.resolved_counts() == {"prefix": {"xla": 4, "pallas_interpret": 1}}
    assert set(_flatten({"attention_impls": impl.resolved_counts()})) == {
        "attention_impls_prefix_xla", "attention_impls_prefix_pallas_interpret",
    }


def test_a_failed_warm_up_decision_ends_the_run_with_its_message(monkeypatch):
    """Set-up retries nothing: an engine error while warming up is the
    smoke's failure, message and all — not a decision to resubmit."""
    from k8s_llm_scheduler_tpu.engine.backend import BackendError
    from k8s_llm_scheduler_tpu.engine.local import LocalLLMBackend

    def broken(self, pod, nodes):
        raise BackendError("engine error: grammar install failed")

    monkeypatch.setattr(LocalLLMBackend, "get_scheduling_decision", broken)
    cfg = chip_smoke.smoke_config(model="tiny", bpe_fixture=False)
    with pytest.raises(BackendError, match="grammar install failed"):
        chip_smoke.run(cfg, nodes=3, pods=12, shapes=6)


def test_a_dead_engine_binds_every_pod_and_fails_the_smoke(monkeypatch):
    """The fallback ladder is product safety code: with the engine dead the
    scheduler still binds every pod. The smoke looks at the SOURCE of each
    decision, so that run fails — naming the fallback and the breaker."""
    from k8s_llm_scheduler_tpu.engine.backend import BackendError
    from k8s_llm_scheduler_tpu.engine.local import LocalLLMBackend

    async def dead(self, pod, nodes, work="prefill"):
        raise BackendError("device lost")

    # set-up warms through the sync seam; the scheduler serves through this one
    monkeypatch.setattr(LocalLLMBackend, "get_scheduling_decision_async", dead)
    cfg = chip_smoke.smoke_config(model="tiny", bpe_fixture=False)
    cfg.data["llm"]["retry_delay"] = 0.01  # test speed only
    summary = chip_smoke.run(cfg, nodes=3, pods=12, shapes=6)
    assert summary["bound"] == 12
    assert summary["decisions_by_source"]["llm"] == 0
    failed = " | ".join(summary["failures"])
    assert "fallback_decisions" in failed and "llm_decisions 0 < 6" in failed
    assert "circuit breaker" in failed


def test_build_local_backend_refuses_an_unnamed_cpu():
    jax.config.update("jax_platforms", "tpu,cpu")  # cpu listed, not asked for
    try:
        with pytest.raises(RuntimeError, match="no accelerator was found"):
            build_local_backend(model="tiny")
    finally:
        jax.config.update("jax_platforms", "cpu")
