"""The py310 lint both works and passes on the tree.

The seed's 20 tier-1 failures all came from one 3.11+-only call
(``asyncio.timeout``) on a 3.10 interpreter; tools/py310_lint.py is the
guard that keeps that class of regression from silently returning. This
test (a) proves the repo is clean and (b) pins the detector's behavior so
the guard itself can't rot.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from tools import py310_lint


class TestRepoIsClean:
    def test_no_py311_only_apis_in_tree(self):
        violations = py310_lint.run()
        assert violations == [], "\n".join(violations)

    def test_scans_a_meaningful_file_set(self):
        files = {str(p.relative_to(py310_lint.REPO_ROOT))
                 for p in py310_lint.iter_py_files()}
        # the original offenders and the compat helper must all be covered
        assert "tests/test_scheduler_loop.py" in files
        assert "tests/test_kube_cluster.py" in files
        assert "tests/test_replica.py" in files
        assert "k8s_llm_scheduler_tpu/testing.py" in files
        assert "bench.py" in files
        # the rollout package (new in the live-rollout round) is covered
        # by the recursive scan — pin it so a SCAN_DIRS refactor can't
        # silently drop it
        assert "k8s_llm_scheduler_tpu/rollout/hotswap.py" in files
        assert "k8s_llm_scheduler_tpu/rollout/registry.py" in files
        assert "tests/test_rollout.py" in files
        # observability round: span tracing + sampler modules (contextvars-
        # heavy async code is exactly where 3.11+-only asyncio APIs creep in)
        assert "k8s_llm_scheduler_tpu/observability/spans.py" in files
        assert "k8s_llm_scheduler_tpu/observability/sampler.py" in files
        assert "k8s_llm_scheduler_tpu/observability/metrics.py" in files
        assert "tests/test_observability.py" in files
        # fleet round: sharded frontend + pools are asyncio-heavy (the
        # same 3.11+-API risk class as the scheduler loop)
        assert "k8s_llm_scheduler_tpu/fleet/lease.py" in files
        assert "k8s_llm_scheduler_tpu/fleet/cache.py" in files
        assert "k8s_llm_scheduler_tpu/fleet/pools.py" in files
        assert "k8s_llm_scheduler_tpu/fleet/frontend.py" in files
        assert "tests/test_fleet.py" in files
        # fleet-telemetry round: profiler / aggregator / SLO engine (the
        # SLO ticker and aggregator pulls are thread+deque-heavy code of
        # the same 3.11+-API risk class as the sampler)
        assert "k8s_llm_scheduler_tpu/observability/profiler.py" in files
        assert "k8s_llm_scheduler_tpu/observability/fleetview.py" in files
        assert "k8s_llm_scheduler_tpu/observability/slo.py" in files
        assert "tests/test_profiler.py" in files
        assert "tests/test_fleetview.py" in files
        assert "tests/test_slo.py" in files
        # chaos round: the fault plane + deadline ladder are contextvar/
        # asyncio-heavy (ambient budgets, wave-barriered runners) — the
        # exact risk class the asyncio.timeout rule exists for
        assert "k8s_llm_scheduler_tpu/chaos/faults.py" in files
        assert "k8s_llm_scheduler_tpu/chaos/invariants.py" in files
        assert "k8s_llm_scheduler_tpu/chaos/harness.py" in files
        assert "k8s_llm_scheduler_tpu/sched/deadline.py" in files
        assert "tests/test_chaos_plane.py" in files
        # learn round: the policy-improvement loop (miner/curriculum/loop
        # drive asyncio arena runs and thread-adjacent registry code —
        # same risk class as rollout/)
        assert "k8s_llm_scheduler_tpu/learn/miner.py" in files
        assert "k8s_llm_scheduler_tpu/learn/curriculum.py" in files
        assert "k8s_llm_scheduler_tpu/learn/loop.py" in files
        assert "tests/test_learn.py" in files
        # admission round: the delta-prefill admission plane (packed
        # chunked prefill + pinned prefix KV + snapshot-delta prompts) —
        # worker-thread + futures-heavy code, the same 3.11+-API risk
        # class as the engine worker it extends
        assert "k8s_llm_scheduler_tpu/engine/admission/packer.py" in files
        assert "k8s_llm_scheduler_tpu/engine/admission/chunked.py" in files
        # durability round: the decision journal + recovery protocol
        # (thread/asyncio-crossing binder wrappers and to_thread
        # recovery — the same 3.11+-API risk class as the scheduler
        # loop they ride)
        assert "k8s_llm_scheduler_tpu/sched/journal.py" in files
        assert "k8s_llm_scheduler_tpu/sched/recovery.py" in files
        assert "tests/test_durable.py" in files
        assert "k8s_llm_scheduler_tpu/engine/admission/pinned.py" in files
        assert "k8s_llm_scheduler_tpu/sched/delta.py" in files
        assert "tests/test_admission.py" in files
        # fused-decode round: the fused runtime (while_loop decode loop,
        # dense tables, on-device sampler) plus the zero-copy replica
        # transport — the transport is thread+futures-heavy (outbox
        # flush protocol), the same 3.11+-API risk class as the worker
        assert "k8s_llm_scheduler_tpu/engine/fused/loop.py" in files
        assert "k8s_llm_scheduler_tpu/engine/fused/sampler.py" in files
        assert "k8s_llm_scheduler_tpu/engine/fused/tables.py" in files
        assert "k8s_llm_scheduler_tpu/sched/replica.py" in files
        assert "tests/test_fused.py" in files
        # autoscale round: the elastic control loop (async fleet ops,
        # tick-driven controller) — the same asyncio-heavy risk class
        # as the scheduler loop it scales
        assert "k8s_llm_scheduler_tpu/fleet/autoscale.py" in files
        assert "tests/test_autoscale.py" in files
        # async-spec round: the rewritten speculative pipeline (round
        # state machine over device futures + the hidden-transfer arm and
        # its training loop) — dataclass/future-heavy code of the same
        # 3.11+-API risk class as the engine worker it composes with
        assert "k8s_llm_scheduler_tpu/spec/decoder.py" in files
        assert "k8s_llm_scheduler_tpu/spec/draft.py" in files
        assert "k8s_llm_scheduler_tpu/spec/verify.py" in files
        assert "k8s_llm_scheduler_tpu/spec/hidden.py" in files
        assert "k8s_llm_scheduler_tpu/train/hidden.py" in files
        assert "tests/test_spec_async.py" in files
        # kvplane round: the shared prefix-KV plane (lease-fenced fills,
        # injected-clock store, host-transport page shipping) — the same
        # clock/lease-heavy risk class as fleet/lease.py it builds on
        assert "k8s_llm_scheduler_tpu/fleet/kvplane/store.py" in files
        assert "k8s_llm_scheduler_tpu/fleet/kvplane/client.py" in files
        assert "k8s_llm_scheduler_tpu/fleet/kvplane/pages.py" in files
        assert "k8s_llm_scheduler_tpu/fleet/kvplane/stub.py" in files
        assert "tests/test_kvplane.py" in files
        # interprocedural-graftlint round: the analysis engine's test
        # file rides the normal scan; the engine's OWN tree is excluded
        # here (rule modules are pattern tables) and covered instead by
        # the self-sweep in tests/test_graftlint.py
        assert "tests/test_graftlint.py" in files
        assert "tools/graftlint/repograph.py" not in files
        assert "tools/graftlint/core.py" not in files
        assert not any(f.startswith("tests/fixtures/graftlint") for f in files)
        # the lint never lints its own pattern table
        assert "tools/py310_lint.py" not in files


class TestDetector:
    # The synthetic bad lines below carry the pragma so the REAL lint run
    # over this very file stays clean; scan_text still sees them raw when
    # the pragma is absent from the scanned text.

    def test_catches_asyncio_timeout_call(self):
        call = "asyncio" + ".timeout(5)"  # assembled: not a lintable literal
        bad = f"async def f():\n    async with {call}:\n        pass\n"
        hits = py310_lint.scan_text(bad, "x.py")
        assert len(hits) == 1 and "x.py:2" in hits[0]

    def test_catches_from_import_spelling(self):
        bad = "from " + "asyncio import timeout\n"
        assert py310_lint.scan_text(bad, "x.py")
        bad2 = "from " + "asyncio import (gather, timeout)\n"
        assert py310_lint.scan_text(bad2, "x.py")

    def test_catches_exception_group_and_except_star(self):
        bad = "raise " + "ExceptionGroup('g', [])\n"  # py310-ok (fixture)
        assert py310_lint.scan_text(bad, "x.py")
        bad2 = "try:\n    pass\n" + "except" + "* ValueError:\n    pass\n"
        hits = py310_lint.scan_text(bad2, "x.py")
        # EXACTLY one, the 3.11+-syntax message: this text does not parse
        # on 3.10, and the historical regex-only contract must not grow a
        # companion parse-error line from the graftlint framework
        assert len(hits) == 1 and "3.11+" in hits[0]

    def test_comment_and_pragma_lines_are_exempt(self):
        call = "asyncio" + ".timeout(5)"
        ok = (
            f"# {call} would be wrong here\n"
            "t = getattr(asyncio, 'timeout', None)\n"
            f"native = {call}  # py310-ok: guarded by version check\n"
        )
        assert py310_lint.scan_text(ok, "x.py") == []

    def test_plain_mentions_without_call_pass(self):
        # prose referencing the API by name (docstrings, comments-in-string
        # edge cases) is not a violation — only call syntax is
        assert py310_lint.scan_text('"""asyncio.timeout is 3.11+"""\n', "x.py") == []
