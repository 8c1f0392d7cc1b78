"""Per-request flight recorder (serving/flightrec.py,
docs/OBSERVABILITY.md): phase attribution partitions the wall clock,
memory is bounded twice (requests + events), terminal events are
exactly-once, and the disabled path touches nothing on the spine."""

from __future__ import annotations

import time

from distributed_inference_server_tpu.core.models import FinishReason, Usage
from distributed_inference_server_tpu.engine.engine import (
    SamplingParams,
    StepOutput,
)
from distributed_inference_server_tpu.serving.flightrec import (
    PHASES,
    FlightRecorder,
)
from distributed_inference_server_tpu.serving.metrics import MetricsCollector
from distributed_inference_server_tpu.serving.runner import (
    EngineRunner,
    ServerRequest,
)
from distributed_inference_server_tpu.utils.tracing import Tracer


def _drive_request(rec, rid="r1", tokens=20, fetch_s=0.0, stall_s=0.0):
    rec.admit(rid, endpoint="generate", prompt_tokens=8,
              trace_id="t" * 16)
    rec.note(rid, "schedule", engine="engine-0", strategy="least_loaded")
    if fetch_s:
        rec.note(rid, "prefix_fetch", outcome="ok", seconds=fetch_s)
    for _ in range(tokens):
        rec.token(rid)
    if stall_s:
        rec.note(rid, "handoff_resume", target="engine-1",
                 stall_s=stall_s)
    return rec.finish(rid, "ok")


class TestPhaseModel:
    def test_phases_partition_wall_clock(self):
        rec = FlightRecorder()
        phases = _drive_request(rec, tokens=40)
        tl = rec.timeline("r1")
        assert set(phases) == set(PHASES)
        total = sum(phases.values())
        # exact partition by construction (clamps never trigger here)
        assert abs(total - tl["wall_s"]) < 1e-6
        assert tl["status"] == "ok" and tl["tokens"] == 40
        assert tl["ttft_s"] is not None and tl["ttft_s"] >= 0
        assert tl["trace_id"] == "t" * 16

    def test_windowed_costs_subtract_from_containing_phase(self):
        rec = FlightRecorder()
        rec.admit("r1")
        rec.note("r1", "schedule", engine="e0")
        time.sleep(0.03)
        # the fetch window lands inside dispatch -> first_token
        rec.note("r1", "prefix_fetch", outcome="ok", seconds=0.02)
        rec.token("r1")
        time.sleep(0.02)
        rec.token("r1")
        rec.note("r1", "handoff_resume", target="e1", stall_s=0.01)
        phases = rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        assert abs(phases["peer_fetch"] - 0.02) < 1e-6
        assert abs(phases["handoff_stall"] - 0.01) < 1e-6
        assert abs(sum(phases.values()) - tl["wall_s"]) < 1e-6
        # the subtraction really happened: prefill excludes the fetch
        assert phases["prefill"] <= tl["wall_s"] - 0.02

    def test_windows_clamp_to_their_span(self):
        # a reported stall larger than the decode window must not make
        # the partition exceed the wall clock
        rec = FlightRecorder()
        rec.admit("r1")
        rec.note("r1", "schedule", engine="e0")
        rec.token("r1")
        rec.token("r1")
        rec.note("r1", "handoff_resume", target="e1", stall_s=999.0)
        phases = rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        assert sum(phases.values()) <= tl["wall_s"] + 1e-6

    def test_mixed_step_prefill_attribution_window_subtraction(self):
        """ISSUE 12: when prefill rides the MIXED step, a request's
        prompt loads across several mixed dispatches while OTHER rows'
        decode tokens interleave on the wall clock — but the per-request
        phase model is unchanged: prefill is still dispatch ->
        first_token minus the fetch windows inside it, the partition
        stays exact, and a peer-fetch window that landed mid-mixed-
        prefill subtracts from prefill, never from decode."""
        rec = FlightRecorder()
        rec.admit("r1", endpoint="generate")
        rec.note("r1", "schedule", engine="e0", strategy="least_loaded")
        # the prompt spreads over mixed dispatches: wall time passes
        # before the first token, with a fetch window inside it
        time.sleep(0.02)
        rec.note("r1", "prefix_fetch", outcome="ok", seconds=0.015)
        time.sleep(0.02)
        rec.token("r1")  # first token: prefill complete
        time.sleep(0.01)
        rec.token("r1")
        phases = rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        # exact partition (window-subtraction did not tear it)
        assert abs(sum(phases.values()) - tl["wall_s"]) < 1e-6
        # the fetch window subtracted from PREFILL, exactly
        assert abs(phases["peer_fetch"] - 0.015) < 1e-6
        assert phases["prefill"] >= 0.04 - 0.015 - 1e-3
        assert phases["prefill"] <= tl["ttft_s"] - 0.015 + 1e-6
        # decode is untouched by the prefill-side window
        assert phases["decode"] >= 0.01 - 1e-3

    def test_looped_block_bursts_keep_partition_exact(self):
        """ISSUE 19 (kernel looping): a run-to-completion decode block
        surfaces a whole block's tokens as one burst at reconcile, and
        block lengths vary (eos / budget / pages / cap exits) — so the
        per-request token cadence is lumpy and the decode window spans
        host-silent stretches. The phase model needs no loop awareness:
        decode is still first_token -> finish minus the windows inside
        it, and the partition stays exact under bursts of any shape."""
        rec = FlightRecorder()
        rec.admit("r1", endpoint="generate")
        rec.note("r1", "schedule", engine="e0", strategy="least_loaded")
        time.sleep(0.01)
        rec.token("r1", 1)  # prefill's token: prefill complete
        # looped blocks reconcile at irregular intervals with
        # variable-size bursts (cap exit, pages exit, final eos)
        for burst, gap in ((8, 0.02), (3, 0.01), (5, 0.015)):
            time.sleep(gap)
            rec.token("r1", burst)
        # a handoff window lands INSIDE the looped-decode stretch
        rec.note("r1", "handoff_resume", target="e1", stall_s=0.012)
        phases = rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        assert tl["tokens"] == 1 + 8 + 3 + 5
        # exact partition: bursts and silent stretches don't tear it
        assert abs(sum(phases.values()) - tl["wall_s"]) < 1e-6
        # the stall window subtracted from DECODE, exactly
        assert abs(phases["handoff_stall"] - 0.012) < 1e-6
        assert phases["decode"] >= 0.045 - 0.012 - 1e-3
        # prefill is untouched by the decode-side window
        assert phases["prefill"] >= 0.01 - 1e-3
        assert phases["prefill"] <= tl["ttft_s"] + 1e-6

    def test_zero_token_error_request(self):
        rec = FlightRecorder()
        rec.admit("r1")
        rec.note("r1", "schedule", engine="e0")
        phases = rec.finish("r1", "error", code="worker_failure")
        assert phases["decode"] == phases["detok"] == 0.0
        tl = rec.timeline("r1")
        assert tl["status"] == "error" and tl["code"] == "worker_failure"

    def test_never_dispatched_request_is_all_queue_wait(self):
        """Review regression: a request that starves in the queue
        (queue_timeout / no_workers — no schedule note ever) must
        attribute its whole window to queue_wait, not to a phantom
        prefill — the misattribution would invert exactly the answer
        this feature exists to give."""
        rec = FlightRecorder()
        rec.admit("r1")
        time.sleep(0.02)
        phases = rec.finish("r1", "error", code="queue_timeout")
        tl = rec.timeline("r1")
        assert phases["prefill"] == 0.0
        assert abs(phases["queue_wait"] - tl["wall_s"]) < 1e-6

    def test_phase_metrics_exported(self):
        m = MetricsCollector()
        rec = FlightRecorder(metrics=m)
        _drive_request(rec)
        snap = m.snapshot().to_dict()
        assert snap["tracing"]["phase_requests"] == 1
        assert set(snap["tracing"]["phase_seconds"]) == set(PHASES)
        prom = m.prometheus_text().decode()
        assert 'request_phase_seconds_count{phase="decode"} 1.0' in prom


class TestBoundedMemory:
    def test_request_eviction_counted(self):
        rec = FlightRecorder(max_requests=4)
        for i in range(10):
            rec.admit(f"r{i}")
            rec.finish(f"r{i}", "ok")
        assert rec.stats()["tracked"] == 4
        assert rec.stats()["evicted"] == 6
        assert rec.timeline("r0") is None  # evicted
        assert rec.timeline("r9") is not None

    def test_event_cap_drops_counted_terminal_always_lands(self):
        rec = FlightRecorder(max_events=5)
        rec.admit("r1")
        for i in range(20):
            rec.note("r1", "schedule", engine=f"e{i}")
        rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        assert tl["events_dropped"] > 0
        assert tl["events"][-1]["name"] == "terminal"

    def test_decode_blocks_not_per_token(self):
        rec = FlightRecorder(block_tokens=16)
        rec.admit("r1")
        rec.note("r1", "schedule", engine="e0")
        for _ in range(40):
            rec.token("r1")
        rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        blocks = [e for e in tl["events"] if e["name"] == "decode_block"]
        # 40 tokens -> 2 full blocks + the terminal flush block
        assert len(blocks) == 3
        assert sum(b["attributes"]["tokens"] for b in blocks) == 40
        assert tl["tokens"] == 40


class TestContracts:
    def test_finish_is_first_wins(self):
        rec = FlightRecorder()
        rec.admit("r1")
        rec.token("r1")
        assert rec.finish("r1", "ok") is not None
        assert rec.finish("r1", "error", code="late") is None
        tl = rec.timeline("r1")
        assert tl["status"] == "ok" and "code" not in tl

    def test_tokens_after_terminal_ignored(self):
        rec = FlightRecorder()
        rec.admit("r1")
        rec.token("r1")
        rec.finish("r1", "ok")
        rec.token("r1")
        assert rec.timeline("r1")["tokens"] == 1

    def test_auto_created_timeline_for_direct_submits(self):
        # requests that bypass the handler (chaos harness, redispatch
        # onto a fresh replica) still get a usable timeline
        rec = FlightRecorder()
        rec.note("r1", "schedule", engine="e0")
        rec.token("r1")
        rec.finish("r1", "ok")
        tl = rec.timeline("r1")
        assert tl is not None and tl["tokens"] == 1

    def test_global_events_merge_into_overlapping_windows(self):
        rec = FlightRecorder()
        rec.admit("r1")
        rec.note_global("rerole", direction="to_prefill")
        rec.finish("r1", "ok")
        # a request admitted AFTER the rerole does not see it
        rec.admit("r2")
        rec.finish("r2", "ok")
        assert any(e["name"] == "rerole"
                   for e in rec.timeline("r1")["fleet_events"])
        assert "fleet_events" not in rec.timeline("r2")

    def test_recent_listing_newest_first(self):
        rec = FlightRecorder()
        for i in range(3):
            rec.admit(f"r{i}")
        listing = rec.recent(2)
        assert [r["request_id"] for r in listing] == ["r2", "r1"]


class TestSpineFastPath:
    """The disabled path: a runner without a recorder/tracer must not
    touch any ring or timeline on the per-token path."""

    def _runner(self, tracer=None, recorder=None):
        # never started: we drive _dispatch directly on this thread,
        # exactly as the engine thread would
        return EngineRunner("e0", engine_factory=None, tracer=tracer,
                            recorder=recorder)

    def _req(self, rid="r1"):
        class Sink:
            def __init__(self):
                self.tokens, self.dones, self.errors = [], 0, []

            def on_token(self, token_id, text, token_index, logprob=None):
                self.tokens.append(token_id)

            def on_done(self, reason, usage):
                self.dones += 1

            def on_error(self, message, code):
                self.errors.append(code)

        sink = Sink()
        req = ServerRequest(rid, [1, 2, 3], SamplingParams(max_tokens=4),
                            sink)
        return req, sink

    def test_disabled_no_ring_writes_no_timelines(self):
        tracer = Tracer()
        recorder = FlightRecorder()
        r = self._runner(tracer=None, recorder=None)
        req, sink = self._req()
        r._inflight[req.request_id] = req
        r._dispatch([StepOutput("r1", token_id=7, text="x")])
        r._dispatch([StepOutput("r1", finished=True,
                                finish_reason=FinishReason.STOP,
                                usage=Usage.of(3, 1))])
        assert sink.dones == 1 and sink.tokens == [7]
        assert tracer.recent() == []  # nothing ever exported
        assert recorder.stats()["tracked"] == 0  # nothing recorded

    def test_enabled_records_tokens_and_terminal(self):
        recorder = FlightRecorder()
        r = self._runner(recorder=recorder)
        req, sink = self._req()
        r._inflight[req.request_id] = req
        r._dispatch([StepOutput("r1", token_id=7, text="x")])
        r._dispatch([StepOutput("r1", finished=True,
                                finish_reason=FinishReason.STOP,
                                usage=Usage.of(3, 1))])
        tl = recorder.timeline("r1")
        assert tl["tokens"] == 1 and tl["status"] == "ok"
        assert any(e["name"] == "first_token" for e in tl["events"])

    def test_error_output_records_terminal(self):
        recorder = FlightRecorder()
        r = self._runner(recorder=recorder)
        req, sink = self._req()
        r._inflight[req.request_id] = req
        r._dispatch([StepOutput("r1", error="boom", finished=True)])
        tl = recorder.timeline("r1")
        assert tl["status"] == "error"
        assert tl["code"] == "inference_failed"
        assert sink.errors == ["inference_failed"]


class TestSloVerdicts:
    """SLO accounting at finish() (serving/teledigest.py SloSettings;
    docs/OBSERVABILITY.md "Performance telemetry")."""

    def _slo(self, **kw):
        from distributed_inference_server_tpu.serving.teledigest import (
            SloSettings,
        )

        return SloSettings(**kw)

    def test_verdict_stamped_and_counted(self):
        m = MetricsCollector()
        rec = FlightRecorder(metrics=m,
                             slo=self._slo(ttft_ms=10_000.0))
        _drive_request(rec, tokens=8)
        tl = rec.timeline("r1")
        assert tl["slo"]["verdict"] == "ok"
        counts, goodput = m.slo_counts()
        assert counts == {"default": {"ok": 1}}
        assert goodput == {"default": 8}
        text = m.prometheus_text().decode()
        assert ('slo_requests_total{tenant="default",verdict="ok"} 1.0'
                in text)
        assert 'slo_goodput_tokens_total{tenant="default"} 8.0' in text

    def test_violation_and_listing_filter(self):
        m = MetricsCollector()
        # 0ms TTFT objective: everything violates
        rec = FlightRecorder(metrics=m, slo=self._slo(ttft_ms=1e-9))
        _drive_request(rec, rid="bad", tokens=4)
        rec.admit("never-slo")  # live request: no verdict yet
        tl = rec.timeline("bad")
        assert tl["slo"]["verdict"] == "violated"
        assert tl["slo"]["ttft_violated"] is True
        # goodput counts only SLO-met requests
        _, goodput = m.slo_counts()
        assert goodput == {}
        # ?verdict= filter: only the violated timeline lists
        listed = rec.recent(50, verdict="violated")
        assert [e["request_id"] for e in listed] == ["bad"]
        assert listed[0]["verdict"] == "violated"
        assert rec.recent(50, verdict="ok") == []
        # unfiltered listing still carries the verdict field
        allr = {e["request_id"]: e for e in rec.recent(50)}
        assert allr["bad"]["verdict"] == "violated"
        assert "verdict" not in allr["never-slo"]

    def test_tenant_rides_admit_attrs(self):
        m = MetricsCollector()
        rec = FlightRecorder(
            metrics=m, slo=self._slo(tenant_ttft_ms={"gold": 1e-9}))
        rec.admit("g1", tenant="gold")
        rec.token("g1")
        rec.finish("g1", "ok")
        rec.admit("d1", tenant="silver")  # no applicable objective
        rec.token("d1")
        assert rec.finish("d1", "ok") is not None
        counts, _ = m.slo_counts()
        assert counts == {"gold": {"violated": 1}}
        assert rec.timeline("d1").get("slo") is None

    def test_error_request_with_slo_is_violation(self):
        m = MetricsCollector()
        rec = FlightRecorder(metrics=m, slo=self._slo(ttft_ms=60_000.0))
        rec.admit("e1")
        rec.note("e1", "schedule", engine="e0")
        rec.token("e1")
        rec.finish("e1", "error", code="engine_crashed")
        assert rec.timeline("e1")["slo"]["verdict"] == "violated"

    def test_no_slo_config_means_no_verdicts(self):
        m = MetricsCollector()
        rec = FlightRecorder(metrics=m)
        _drive_request(rec, tokens=4)
        assert "slo" not in rec.timeline("r1")
        counts, _ = m.slo_counts()
        assert counts == {}

    def test_tbt_digest_fed_at_finish(self):
        m = MetricsCollector()
        rec = FlightRecorder(metrics=m)
        _drive_request(rec, tokens=16)
        wires = m.perf.wire_digests()
        assert wires["tbt_ms"]["ring"]
        assert wires["queue_wait_ms"]["ring"]
