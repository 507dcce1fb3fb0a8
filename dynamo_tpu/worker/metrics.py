"""Worker-side Prometheus metrics, served on the worker's system server.

Until now admission/migration/disagg signals existed only as frontend
metrics (``http/metrics.py``); a worker's own ``/metrics``
(``DYN_SYSTEM_ENABLED=1``, ``runtime/system_server.py``) showed nothing about
the requests it actually absorbed.  This registry closes that gap:

- ``dynamo_worker_requests_total{outcome}`` — requests by admission outcome:
  ``admitted``, ``refused_expired`` (deadline already passed on arrival),
  ``deadline_cancelled`` (expired mid-generation), ``error``.
- ``dynamo_worker_migration_replays_total{mode}`` — migrated streams this
  worker ABSORBED (requests re-issued by a frontend after another worker
  dropped or drained the stream; stamped via
  ``PreprocessedRequest.migration_attempt``): ``resume`` rode a pinned-KV
  resume token, ``replay`` recomputed from scratch.
- ``dynamo_worker_drain_state`` / ``dynamo_worker_migrated_sequences_total``
  — the graceful-drain lifecycle (``worker/drain.py``): drain progress and
  how many in-flight sequences were handed off resumable vs replayed.
- ``dynamo_worker_disagg_kv_bytes_total{direction,plane}`` — disagg KV block
  bytes moved, by direction (``pulled``) and transport plane
  (``direct``/``bulk``/``rpc``) — the FlowKV-dominant cost made visible.
- ``dynamo_tpu_stage_duration_seconds{stage}`` — per-stage latency breakdown
  (queue/prefill/kv_transfer/decode/...), observed from locally-finished
  trace spans (``http/metrics.StageMetrics`` listener), the same series the
  frontend registers so dashboards join on one name.

A process-wide singleton (``get_worker_metrics``) because the handler
factories (``llm/register.engine_handler``) and the disagg handlers have no
shared construction point; the worker main passes its registry to the
system server.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

from prometheus_client import CollectorRegistry, Counter, Gauge
from prometheus_client.core import (CounterMetricFamily, GaugeMetricFamily,
                                    HistogramMetricFamily)

from dynamo_tpu.http.metrics import StageMetrics, loop_lag_histogram


class KvbmStatsCollector:
    """Scrape-time collector mapping ``TieredEngine.kvbm_stats()`` onto
    ``dynamo_worker_kvbm_*`` gauges/counters.

    Registered UNCONDITIONALLY (zero-valued until a tiered engine is
    attached via :meth:`attach`) so the metrics<->docs drift gate
    (``tools/check_metrics_docs.py``) always sees the full surface, and a
    worker without tiers still exposes a stable schema."""

    # kvbm_stats key -> help text; metric name = "dynamo_worker_" + key
    GAUGES: Dict[str, str] = {
        "kvbm_host_blocks": "KV blocks resident in the G2 host-RAM tier",
        "kvbm_host_bytes": "Bytes used by the G2 host-RAM tier",
        "kvbm_disk_blocks": "KV blocks resident in the G3 disk tier",
        "kvbm_disk_bytes": "Bytes used by the G3 disk tier",
        "kvbm_pending_spills": "Eviction batches waiting in the bounded "
                               "background spill queue",
        "kvbm_prefetch_pinned_pages": "Pages currently pinned by prefetch "
                                      "promotion leases (released when the "
                                      "request commits or aborts)",
        "kvbm_prefetch_inflight": "Requests with a live lookahead "
                                  "promotion task",
    }
    COUNTERS: Dict[str, str] = {
        "kvbm_offloaded_blocks": "Blocks offloaded G1->G2 on eviction",
        "kvbm_onboarded_blocks": "Tier blocks injected back into HBM "
                                 "(synchronous fast path + prefetch)",
        "kvbm_dropped_spills": "Spill batches dropped because the bounded "
                               "queue was full (tiers are best-effort)",
        "kvbm_peer_onboarded_blocks": "Blocks onboarded from the G4 peer "
                                      "tier on a local tier miss",
        "kvbm_disk_corrupt_dropped": "Disk-tier entries rejected by length/"
                                     "crc32 verification on read (treated "
                                     "as a miss, evicted — never injected)",
        "kvbm_prefetch_hits": "Blocks the prefetch scheduler promoted "
                              "ahead of the prefill cursor",
        "kvbm_prefetch_late": "Prefetch promotions that lost the race (the "
                              "block was already resident, or no pages "
                              "were free for it)",
        "kvbm_prefetch_misses": "Planned blocks that fell out of every "
                                "tier before promotion reached them",
        "kvbm_prefetch_evicted_pinned": "Canary: pinned prefetched blocks "
                                        "missing from HBM at release time "
                                        "(must stay 0)",
        "kvbm_prefetch_bytes": "Bytes of KV promoted by the prefetch "
                               "scheduler",
        "kvbm_prefetch_adopted_blocks": "Blocks adopted mid-prefill from "
                                        "the prefix cache instead of "
                                        "recomputed",
    }

    def __init__(self, registry: CollectorRegistry):
        self._source: Optional[Callable[[], Dict[str, float]]] = None
        registry.register(self)

    def attach(self, source: Callable[[], Dict[str, float]]) -> None:
        """Point the collector at a live ``kvbm_stats`` provider."""
        self._source = source

    def collect(self):
        stats: Dict[str, float] = {}
        if self._source is not None:
            try:
                stats = self._source() or {}
            except Exception:  # noqa: BLE001 — a scrape must never fail
                import logging
                logging.getLogger(__name__).debug(
                    "kvbm stats sample failed", exc_info=True)
        for key, help_text in self.GAUGES.items():
            yield GaugeMetricFamily(f"dynamo_worker_{key}", help_text,
                                    value=float(stats.get(key, 0)))
        for key, help_text in self.COUNTERS.items():
            yield CounterMetricFamily(f"dynamo_worker_{key}", help_text,
                                      value=float(stats.get(key, 0)))


class EngineDispatchCollector:
    """Scrape-time collector mapping the engine's dispatch taps onto
    ``dynamo_worker_decode_*`` counters (the PR 5 scatter-tap style:
    counts of jitted dispatches, not timing walls).

    Registered UNCONDITIONALLY (zero-valued until an engine is attached)
    so the metrics<->docs drift gate always sees the schema."""

    COUNTERS: Dict[str, str] = {
        "decode_dispatches": "Decode-family jitted dispatches (per-step, "
                             "chained, spec-verify, mixed, and fused "
                             "multi-step blocks each count ONE) — with "
                             "fusion on, M decoded tokens cost ~M/width "
                             "dispatches",
        "decode_multistep_blocks": "Fused multi-step decode blocks "
                                   "dispatched (DYN_DECODE_MULTISTEP steps "
                                   "per block before scheduler narrowing)",
        "mixed_dispatches": "Mixed prefill+decode dispatches (prefill "
                            "chunks and decode rows advanced in ONE "
                            "ragged [B, S] step, DYN_MIXED_BATCH)",
        "packed_decode_kernel_rows": "Rows of token-packed steps that the "
                                     "decode kernel attended: a packed "
                                     "step's trailing one-token rows (the "
                                     "ragged kernel takes the rows of "
                                     "several tokens); stays 0 where a "
                                     "model's visibility block or latent "
                                     "attention keeps every row in one "
                                     "kernel",
        "guided_parity_mismatches": "Guided rows whose host-side automaton "
                                    "re-walk disagreed with the device "
                                    "transition table after a fused block "
                                    "(logged once per row; any nonzero "
                                    "value is a device/host lowering bug)",
        "sched_admission_run_steps": "Prefill-carrying (mixed) steps in the "
                                     "admission runs counted by "
                                     "dynamo_worker_sched_admission_runs_"
                                     "total: steps / runs is a run's mean "
                                     "length (1.0 where no queue stands "
                                     "behind an admission)",
        "preemptions": "Running sequences evicted back to the waiting "
                       "queue under page pressure (their prompt is computed "
                       "again on re-admission, less what the prefix cache "
                       "kept)",
        "moe_assignments": "Token-to-expert assignments the router made "
                           "(tokens x experts per token x expert layers; "
                           "slots that hold no token route nowhere and are "
                           "not counted)",
        "moe_held_assignments": "Assignments to an expert this worker "
                                "holds: the ones its grouped expert layer "
                                "computed (all of them unless the model "
                                "directory names a rank of an "
                                "expert-parallel deployment)",
        "moe_zero_assignments": "Assignments to a zero-compute expert: "
                                "the token itself times the weight, no "
                                "row, no fetch, no FLOP",
        "moe_experts_touched": "Experts with at least one assignment, "
                               "summed over expert layers and forward "
                               "passes: what the grouped layer read",
        "moe_expert_slots": "Experts the grouped layer could have read: "
                            "forward passes x expert layers x experts "
                            "held (the denominator of the touched share)",
        "gen_tokens_revealed": "Generation by diffusion over blocks: "
                               "masked positions the passes revealed "
                               "(tokens revealed / row-passes = tokens a "
                               "forward pass yields a row)",
        "gen_blocks_committed": "Generation by diffusion over blocks: "
                                "blocks whose committing pass ran (their "
                                "tokens are emitted, their pages hashed "
                                "and published, only then)",
        "gen_rows_waited": "Generation by diffusion over blocks: running "
                           "rows that waited through an admission step "
                           "(a prefill step carries no row in mid-block)",
    }

    # a row's pass either reveals positions of its block or commits it
    PASS_KINDS = ("reveal", "commit")

    # what a block-diffusion engine refuses at admission
    # (engine/jax_engine.py _refusal), pre-seeded like the fallback reasons
    REFUSAL_REASONS = ("guided", "penalties", "logit_bias", "disagg_prefill")

    # the known fallback reasons, pre-seeded so every label shows on the
    # scrape at 0 and dashboards/alerts can reference them before the
    # first refusal happens. "mesh" is GONE on purpose: sharded engines
    # run the fused block program (explicit in/out shardings) — a mesh
    # engine reporting fallbacks again would be a regression, and the
    # parity suite asserts the counter stays 0 there. "penalties" and
    # "guided" now only fire when the device path is unavailable
    # (penalty_window=0 / no grammar lowering); "penalty_window" counts
    # rows whose distinct-token set outgrew the configured ring buffer,
    # "guided_table" grammars whose transition table exceeded the byte
    # cap (JaxEngineConfig.guided_table_bytes) — both per-batch, not
    # per-deployment.
    FALLBACK_REASONS = ("waiters", "prefill", "penalties",
                        "penalty_window", "guided", "guided_table",
                        "spec", "budget", "pages", "multihost")

    # what can end a run of prefill-carrying steps (engine/scheduler.py
    # RUN_ENDS), pre-seeded likewise
    RUN_ENDS = ("queue", "rows", "pages", "partial")

    # what a fused block, and what a mixed step, can be chained behind
    # on the device, and why a chain behind a mixed step is not taken
    # (engine/scheduler.py CHAIN_REFUSALS), pre-seeded likewise
    CHAINED_BEHIND = ("block", "mixed")
    MIXED_CHAINED_BEHIND = ("mixed",)
    CHAIN_REFUSALS = ("run", "rows", "pcarry", "budget", "pages")

    # the forms a prefill-carrying step can take (engine/jax_engine.py
    # _why_padded, and "ring" per plan), pre-seeded like the fallback
    # reasons
    PREFILL_FORMS = ("packed", "padded:forward", "padded:family",
                     "padded:dp", "padded:spec", "padded:attn_impl",
                     "padded:ring")
    # the forms a one-token row attends a learned selection in
    # (engine/jax_engine.py ``one_token_form``)
    ONE_TOKEN_FORMS = ("masked", "gathered")

    def __init__(self, registry: CollectorRegistry):
        self._source: Optional[Callable[[], Dict[str, float]]] = None
        registry.register(self)

    def attach(self, source: Callable[[], Dict[str, float]]) -> None:
        """Point the collector at a live engine's dispatch counters."""
        self._source = source

    def collect(self):
        stats: Dict[str, float] = {}
        if self._source is not None:
            try:
                stats = self._source() or {}
            except Exception:  # noqa: BLE001 — a scrape must never fail
                import logging
                logging.getLogger(__name__).debug(
                    "engine dispatch sample failed", exc_info=True)
        for key, help_text in self.COUNTERS.items():
            yield CounterMetricFamily(f"dynamo_worker_{key}", help_text,
                                      value=float(stats.get(key, 0)))
        # why the fused multi-step path was refused, by reason — the
        # ROADMAP "fallback-reason near zero" criterion, measurable
        fb = CounterMetricFamily(
            "dynamo_worker_multistep_fallback",
            "Fused multi-step decode refusals by reason (waiters/prefill "
            "only with DYN_MIXED_BATCH=0; penalties/guided/spec/budget/"
            "pages from the block planner; multihost from the engine "
            "mode — mesh-sharded engines fuse and never fall back)",
            labels=["reason"])
        reasons = dict.fromkeys(self.FALLBACK_REASONS, 0.0)
        reasons.update(stats.get("multistep_fallbacks") or {})
        for reason, value in sorted(reasons.items()):
            fb.add_metric([str(reason)], float(value))
        yield fb
        runs = CounterMetricFamily(
            "dynamo_worker_sched_admission_runs",
            "Runs of consecutive prefill-carrying (mixed) steps, by what "
            "bounded the run: 'queue' (nothing waits any more), or, with "
            "requests still waiting, what stopped its admission short of "
            "them: 'rows' (max_num_seqs), 'pages' (the pool, less what "
            "the admitted rows will ask for) or 'partial' (the run took "
            "the prompts one admission pass may and the rest of them "
            "does not fill a step)",
            labels=["ended_by"])
        ends = dict.fromkeys(self.RUN_ENDS, 0.0)
        ends.update(stats.get("admission_runs") or {})
        for ended_by, value in sorted(ends.items()):
            runs.add_metric([str(ended_by)], float(value))
        yield runs
        chained = CounterMetricFamily(
            "dynamo_worker_multistep_chained",
            "Fused multi-step blocks whose first tokens came from the "
            "device, by what they were chained behind: 'block' (the "
            "previous block's carry) or 'mixed' (the packed output of the "
            "prefill-carrying step that ended an admission run, still in "
            "flight when the block was enqueued); the rest of "
            "dynamo_worker_decode_multistep_blocks_total were built from "
            "host state with the device waiting",
            labels=["behind"])
        behind = dict.fromkeys(self.CHAINED_BEHIND, 0.0)
        behind.update(stats.get("chained_blocks") or {})
        for what, value in sorted(behind.items()):
            chained.add_metric([str(what)], float(value))
        yield chained
        steps = CounterMetricFamily(
            "dynamo_worker_mixed_chained",
            "Prefill-carrying (mixed) steps whose decode rows' tokens came "
            "from the device, by what they were chained behind: 'mixed' "
            "(the packed output of the mixed step in front of them in "
            "their admission run, still in flight when they were "
            "enqueued); the rest of dynamo_worker_mixed_dispatches_total "
            "(every run's first step among them: it admits) were built "
            "from host state",
            labels=["behind"])
        behind = dict.fromkeys(self.MIXED_CHAINED_BEHIND, 0.0)
        behind.update(stats.get("chained_steps") or {})
        for what, value in sorted(behind.items()):
            steps.add_metric([str(what)], float(value))
        yield steps
        refused = CounterMetricFamily(
            "dynamo_worker_multistep_chain_refused",
            "Mixed steps behind which what follows (the fused block, or "
            "the next mixed step of the admission run) was NOT chained on "
            "the device, by reason: 'rows' (a row was cancelled, or runs "
            "outside the step), 'pcarry' (a row's penalty window or "
            "guided automaton state is built on the host and would lack "
            "the token in flight), 'run' (the run goes on with a step "
            "the device does not chain: a prompt for the ring is next), "
            "'budget' / 'pages' "
            "(the planner refused with that token counted: it never "
            "preempts and never admits); the step and what follows it "
            "then run as they did before the chain existed",
            labels=["reason"])
        why = dict.fromkeys(self.CHAIN_REFUSALS, 0.0)
        why.update(stats.get("chain_refusals") or {})
        for reason, value in sorted(why.items()):
            refused.add_metric([str(reason)], float(value))
        yield refused
        # the pool of slots of a family that keeps one a sequence beside
        # its pages - the recurrent state of linear-attention layers, the
        # rings of window layers - (both 0 for every other family), and
        # the prefix lookups such a family does not make
        yield GaugeMetricFamily(
            "dynamo_worker_state_slots_total",
            "Slots of the recurrent-state pool or of the window rings "
            "(--state-slots): requests of a model with linear-attention "
            "or window layers that can be admitted at once; 0 for a model "
            "whose cache is its pages",
            value=float(stats.get("state_slots_total", 0)))
        yield GaugeMetricFamily(
            "dynamo_worker_state_slots_in_use",
            "Slots of the recurrent-state pool or the window rings held "
            "by admitted requests "
            "(given at admission, taken back at finish and at preemption)",
            value=float(stats.get("state_slots_in_use", 0)))
        pr = CounterMetricFamily(
            "dynamo_worker_prefix_reuse_refused",
            "Admissions whose prefix lookup was not made because a hit "
            "could not be used, by reason: 'recurrent_state' (the model "
            "keeps a state beside the paged cache; a prefix's pages "
            "without the state that matches them are a wrong answer, so "
            "the whole prompt is computed), 'window_cache' (the model's "
            "window layers keep a ring a sequence, which a prefix's pages "
            "do not hold)",
            labels=["reason"])
        why = {"recurrent_state": 0.0, "window_cache": 0.0}
        why.update(stats.get("prefix_reuse_refused") or {})
        for reason, value in sorted(why.items()):
            pr.add_metric([str(reason)], float(value))
        yield pr
        # what the full-attention layers' queries could see and what they
        # attended (the same but where a layer attends a learned
        # selection), and the device bytes of each kind of cache
        yield CounterMetricFamily(
            "dynamo_worker_attn_visible_keys",
            "Keys the queries of ONE full-attention layer could see (a "
            "token at position p sees p + 1), counted for a model that "
            "keeps a slot a sequence beside its pages or whose layers "
            "attend a learned selection (with or without a slot); an "
            "indexer scores every one of them",
            value=float(stats.get("attn_visible_keys", 0)))
        yield CounterMetricFamily(
            "dynamo_worker_attn_selected_keys",
            "Keys the queries of ONE full-attention layer attended where "
            "the layer attends a learned selection (min(index_topk, p + "
            "1) for a token at position p); 0 for every other model",
            value=float(stats.get("attn_selected_keys", 0)))
        yield CounterMetricFamily(
            "dynamo_worker_state_bytes",
            "Bytes of recurrent state the dispatches of a model with "
            "linear-attention layers read and wrote: every linear layer's "
            "float32 slot once in and once out for each row-step (the "
            "ring's state_bytes); 0 for every other model",
            value=float(stats.get("state_bytes", 0)))
        one = CounterMetricFamily(
            "dynamo_worker_attn_one_token_rows",
            "Rows of ONE token that the dispatches of a model whose "
            "full-attention layers attend a learned selection carried (a "
            "decode row a step; a fused block: its rows times its width), "
            "by the form they attended the selection in: 'masked' (the "
            "row's context streamed through the decode kernel of its cache "
            "- latent or grouped-query - with the selection as a bias: the "
            "Pallas kernels), 'gathered' (the "
            "selected rows fetched by a sorted list: the XLA path); both 0 "
            "for every other model",
            labels=["form"])
        forms = dict.fromkeys(self.ONE_TOKEN_FORMS, 0.0)
        forms.update(stats.get("attn_one_token_rows") or {})
        for form, value in sorted(forms.items()):
            one.add_metric([str(form)], float(value))
        yield one
        cb = GaugeMetricFamily(
            "dynamo_worker_cache_bytes",
            "Device bytes of the engine's cache by kind: 'paged' (the "
            "page pool of the attention layers), 'state' (recurrent "
            "state and convolution inputs, a slot a sequence), 'index' "
            "(an indexer's key pages, addressed by the page table of the "
            "keys and values: one block chain holds both), "
            "'window' (window layers' rings, a slot a sequence)",
            labels=["kind"])
        for kind, value in sorted((stats.get("cache_bytes") or {}).items()):
            cb.add_metric([str(kind)], float(value))
        yield cb
        # prefill-carrying steps by the form they ran in, so a model that
        # silently serves padded shows on the scrape
        pf = CounterMetricFamily(
            "dynamo_worker_prefill_steps",
            "Prefill-carrying dispatches (mixed steps and chunked-prefill "
            "steps) by form: 'packed' (one [T] token axis for prompt "
            "chunks and decode rows) or 'padded:<reason>' ([rows x longest "
            "chunk]; reason forward/family/dp/spec/attn_impl for the "
            "engine, ring for a sequence-parallel whole-prompt step)",
            labels=["form"])
        forms = dict.fromkeys(self.PREFILL_FORMS, 0.0)
        forms.update(stats.get("prefill_steps") or {})
        for form, value in sorted(forms.items()):
            pf.add_metric([str(form)], float(value))
        yield pf
        gp = CounterMetricFamily(
            "dynamo_worker_gen_passes",
            "Generation by diffusion over blocks: forward passes summed "
            "over the rows each served (a pass serves every live row, "
            "each in its own phase), by what the pass did to the row's "
            "block: 'reveal' (it held masks; some were revealed) or "
            "'commit' (it held none: the pass wrote its final keys and "
            "values and the block's tokens were emitted)",
            labels=["kind"])
        kinds = dict.fromkeys(self.PASS_KINDS, 0.0)
        kinds.update(stats.get("gen_passes") or {})
        for kind, value in sorted(kinds.items()):
            gp.add_metric([str(kind)], float(value))
        yield gp
        rf = CounterMetricFamily(
            "dynamo_worker_requests_refused",
            "Requests the engine refused at admission because the "
            "model's generation rule does not compose with what they "
            "ask for, by reason (guided / penalties / logit_bias / "
            "disagg_prefill on a model that generates by diffusion over "
            "blocks); the frontend answers the same requests with HTTP "
            "400 before they reach a worker",
            labels=["reason"])
        refused = dict.fromkeys(self.REFUSAL_REASONS, 0.0)
        refused.update(stats.get("requests_refused") or {})
        for reason, value in sorted(refused.items()):
            rf.add_metric([str(reason)], float(value))
        yield rf


class StepTraceCollector:
    """Scrape-time collector rendering the engine step flight recorder's
    inline aggregates (``engine/steptrace.StepRecorder.aggregates()``) as
    the fleet accounting layer: per-kind step duration / batch-occupancy
    histograms, the step-gap histogram (host overhead between
    dispatches), page-pool pressure gauges, and compile-event counters.

    Registered UNCONDITIONALLY (zero-valued until a recorder is attached)
    so the metrics<->docs drift gate always sees the schema. The recorder
    does the bucketing inline on the hot path; this collector only
    re-renders plain dicts at scrape time — a scrape never touches the
    step loop."""

    # the dispatch families the loop stamps; pre-seeded so dashboards can
    # reference every kind before the first dispatch of that kind runs
    KINDS = ("prefill", "decode", "chained", "multistep", "mixed", "spec",
             "gather")

    def __init__(self, registry: CollectorRegistry):
        self._source = None
        registry.register(self)

    def attach(self, source) -> None:
        """Point the collector at a live recorder's ``aggregates``."""
        self._source = source

    @staticmethod
    def _zero_hist(bounds) -> list:
        return [(str(b), 0) for b in bounds] + [("+Inf", 0)]

    def collect(self):
        agg: Dict[str, object] = {}
        if self._source is not None:
            try:
                agg = self._source() or {}
            except Exception:  # noqa: BLE001 — a scrape must never fail
                import logging
                logging.getLogger(__name__).debug(
                    "steptrace aggregate sample failed", exc_info=True)
        from dynamo_tpu.engine.steptrace import (_DUR_BOUNDS, _GAP_BOUNDS,
                                                 _OCC_BOUNDS, _STAGE_BOUNDS,
                                                 STAGES)
        dur = HistogramMetricFamily(
            "dynamo_worker_step_duration_seconds",
            "Device time of one dispatch by kind (prefill/decode/chained/"
            "multistep/mixed/spec/gather), as the host sees it: from the "
            "later of its enqueue and the previous result's arrival to "
            "its own result's arrival (StepRecord.device_ms) — includes "
            "compile time on a fresh jit bucket",
            labels=["kind"])
        occ = HistogramMetricFamily(
            "dynamo_worker_step_occupancy",
            "Batch occupancy per dispatch: real tokens / padded tokens "
            "(bucket-padding waste is 1 - occupancy), by kind",
            labels=["kind"])
        durs = dict(agg.get("duration") or {})
        occs = dict(agg.get("occupancy") or {})
        for kind in sorted(set(self.KINDS) | set(durs) | set(occs)):
            b, s, _n = durs.get(kind) or (self._zero_hist(_DUR_BOUNDS),
                                          0.0, 0)
            dur.add_metric([kind], buckets=b, sum_value=s)
            b, s, _n = occs.get(kind) or (self._zero_hist(_OCC_BOUNDS),
                                          0.0, 0)
            occ.add_metric([kind], buckets=b, sum_value=s)
        yield dur
        yield occ
        gap = HistogramMetricFamily(
            "dynamo_worker_step_gap_seconds",
            "Host time between the end of one dispatch and the start of "
            "the next while work was available (scheduler planning, token "
            "processing, exclusive-window stalls — idle waits excluded)")
        gb, gs, _gn = (agg.get("gap")
                       or (self._zero_hist(_GAP_BOUNDS), 0.0, 0))
        gap.add_metric([], buckets=gb, sum_value=gs)
        yield gap
        stage = HistogramMetricFamily(
            "dynamo_worker_dispatch_stage_seconds",
            "The host's side of one dispatch by stage: handover (the "
            "loop's thread to the worker thread), assemble (plan to host "
            "arrays), upload (host arrays to device arrays), enqueue (the "
            "jitted call until it returns), wait (a synchronous kind: the "
            "call's return to the result on the host) and resume (the "
            "call's return to the event loop's coroutine); each over the "
            "dispatches in which it opened",
            labels=["stage"])
        stages = dict(agg.get("stage") or {})
        for name in STAGES:
            b, s, _n = stages.get(name) or (self._zero_hist(_STAGE_BOUNDS),
                                            0.0, 0)
            stage.add_metric([name], buckets=b, sum_value=s)
        yield stage
        yield GaugeMetricFamily(
            "dynamo_worker_page_pool_free_pages",
            "Free KV pages at the most recent dispatch's plan time",
            value=float(agg.get("pool_free", 0)))
        yield GaugeMetricFamily(
            "dynamo_worker_page_pool_pinned_pages",
            "KV pages pinned under export leases at the most recent "
            "dispatch's plan time",
            value=float(agg.get("pool_pinned", 0)))
        ev = CounterMetricFamily(
            "dynamo_worker_compile_events",
            "XLA compiles detected mid-run (first call on a fresh "
            "(kind, batch, seq) jit bucket), by dispatch kind",
            labels=["kind"])
        secs = CounterMetricFamily(
            "dynamo_worker_compile_seconds",
            "Wall seconds spent in mid-run XLA compiles, by dispatch kind",
            labels=["kind"])
        cev = dict(agg.get("compile_events") or {})
        csec = dict(agg.get("compile_seconds") or {})
        for kind in sorted(set(self.KINDS) | set(cev) | set(csec)):
            ev.add_metric([kind], float(cev.get(kind, 0)))
            secs.add_metric([kind], float(csec.get(kind, 0.0)))
        yield ev
        yield secs
        wait = CounterMetricFamily(
            "dynamo_worker_loop_wait_seconds",
            "Seconds the engine loop spent with nothing to dispatch: idle "
            "(no request queued or running) or blocked (requests waiting, "
            "KV cache full)",
            labels=["state"])
        waits = dict(agg.get("loop_wait_s") or {})
        for state in ("idle", "blocked"):
            wait.add_metric([state], float(waits.get(state, 0.0)))
        yield wait


def engine_dispatch_stats(engine) -> Dict[str, object]:
    """The ``EngineDispatchCollector.attach`` source for a
    ``ScheduledEngineBase`` engine (JaxEngine and the mocker both carry
    the counters). Values are floats, except ``multistep_fallbacks``,
    ``admission_runs``, ``chained_blocks``, ``chained_steps``,
    ``chain_refusals``, ``attn_one_token_rows`` and
    ``prefill_steps``: per-label count dicts the collector renders as
    labeled families."""
    sched = getattr(engine, "scheduler", None)
    moe = engine.moe_counts() if hasattr(engine, "moe_counts") else {}
    gen = getattr(engine, "gen_counts", None) or {}
    return {
        "moe_assignments": float(moe.get("moe_assignments", 0)),
        "moe_held_assignments": float(moe.get("moe_held_assignments", 0)),
        "moe_zero_assignments": float(moe.get("moe_zero_assignments", 0)),
        "moe_experts_touched": float(moe.get("moe_experts_touched", 0)),
        "moe_expert_slots": float(moe.get("moe_expert_slots", 0)),
        "decode_dispatches": float(getattr(engine, "decode_dispatches", 0)),
        "decode_multistep_blocks": float(
            getattr(engine, "multistep_blocks", 0)),
        "mixed_dispatches": float(getattr(engine, "mixed_steps", 0)),
        "packed_decode_kernel_rows": float(
            getattr(engine, "packed_decode_kernel_rows", 0)),
        "state_slots_total": float(getattr(engine, "state_slots", 0)),
        "state_slots_in_use": float(
            getattr(engine, "state_slots", 0)
            - len(getattr(sched, "_free_slots", ()))),
        "prefix_reuse_refused": dict(
            getattr(sched, "prefix_reuse_refused", None) or {}),
        "attn_visible_keys": float(getattr(engine, "attn_visible_keys", 0)),
        "attn_selected_keys": float(
            getattr(engine, "attn_selected_keys", 0)),
        "state_bytes": float(getattr(engine, "state_bytes_moved", 0)),
        "attn_one_token_rows": dict(
            getattr(engine, "attn_one_token_rows", None) or {}),
        "cache_bytes": dict(getattr(engine, "cache_bytes", None) or {}),
        "guided_parity_mismatches": float(
            getattr(engine, "guided_parity_mismatches", 0)),
        "multistep_fallbacks": dict(
            getattr(sched, "multistep_fallbacks", None) or {}),
        "admission_runs": dict(getattr(sched, "admission_runs", None) or {}),
        "chained_blocks": dict(getattr(sched, "chained_blocks", None) or {}),
        "chained_steps": dict(getattr(sched, "chained_steps", None) or {}),
        "chain_refusals": dict(getattr(sched, "chain_refusals", None) or {}),
        "sched_admission_run_steps": float(
            getattr(sched, "admission_run_steps", 0)),
        "preemptions": float(getattr(sched, "num_preemptions", 0)),
        "prefill_steps": dict(getattr(engine, "prefill_steps", None) or {}),
        "gen_passes": {k: float(gen.get(f"passes_{k}", 0))
                       for k in EngineDispatchCollector.PASS_KINDS},
        "gen_tokens_revealed": float(gen.get("tokens_revealed", 0)),
        "gen_blocks_committed": float(gen.get("blocks_committed", 0)),
        "gen_rows_waited": float(getattr(sched, "gen_rows_waited", 0)),
        "requests_refused": dict(
            getattr(engine, "requests_refused", None) or {}),
    }


class WorkerMetrics:
    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        ns = "dynamo_worker"
        self.requests_total = Counter(
            f"{ns}_requests_total",
            "Requests by admission outcome (admitted, refused_expired, "
            "deadline_cancelled, error)",
            ["outcome"], registry=self.registry)
        self.migration_replays = Counter(
            f"{ns}_migration_replays_total",
            "Migrated streams absorbed (re-issued by a frontend after "
            "another worker dropped or drained them), by mode: 'resume' "
            "carries a pinned-KV resume token (no recomputed prefill), "
            "'replay' recomputes from scratch",
            ["mode"], registry=self.registry)
        # -- graceful drain ---------------------------------------------
        self.drain_state = Gauge(
            f"{ns}_drain_state",
            "Worker lifecycle state: 0 serving, 1 draining (in-flight "
            "streams being frozen/handed off), 2 drained (migration "
            "complete or timed out; about to exit)",
            registry=self.registry)
        self.migrated_sequences = Counter(
            f"{ns}_migrated_sequences_total",
            "In-flight sequences this worker handed off during a graceful "
            "drain, by outcome: 'ok' shipped a pinned-KV resume token, "
            "'fallback' shipped a replay marker (nothing committed yet, "
            "or the engine cannot export KV)",
            ["outcome"], registry=self.registry)
        # pre-seed the label sets so every mode/outcome shows on the
        # scrape at 0 (dashboards/alerts can reference them before the
        # first drain happens)
        for mode in ("replay", "resume"):
            self.migration_replays.labels(mode)
        for outcome in ("ok", "fallback"):
            self.migrated_sequences.labels(outcome)
        self.disagg_kv_bytes = Counter(
            f"{ns}_disagg_kv_bytes_total",
            "Disaggregated-prefill KV block bytes transferred, by direction "
            "and transport plane (direct/bulk/rpc)",
            ["direction", "plane"], registry=self.registry)
        # -- data-plane fault tolerance ---------------------------------
        self.kv_exports_active = Gauge(
            f"{ns}_kv_exports_active",
            "KV export leases currently pinning pages for a pending pull "
            "(returns to 0 once pullers ack or the TTL GC reclaims)",
            registry=self.registry)
        self.kv_exports_reclaimed = Counter(
            f"{ns}_kv_exports_reclaimed_total",
            "Export leases reclaimed by the TTL GC sweep (the puller "
            "crashed or never acked — orphaned KV bounded, not leaked)",
            registry=self.registry)
        self.prefill_jobs = Counter(
            f"{ns}_prefill_jobs_total",
            "Prefill queue jobs by outcome (ok, failed, stale — dropped "
            "because the job outlived the decode side's reply timeout)",
            ["outcome"], registry=self.registry)
        self.kv_offer_acks = Counter(
            f"{ns}_kv_offer_acks_total",
            "Device-direct offer acks by outcome (ok, failed — a failed "
            "ack leaves the peer's pinned HBM to its offer TTL)",
            ["outcome"], registry=self.registry)
        self.kv_frames_corrupt = Counter(
            f"{ns}_kv_frames_corrupt_total",
            "Wire-v4 KV frames rejected by checksum verification before "
            "staging (corrupted/truncated in transit; never injected)",
            registry=self.registry)
        self.kv_pull_resumes = Counter(
            f"{ns}_kv_pull_resumes_total",
            "KV block pulls resumed after a mid-pull failure, re-pulling "
            "only the blocks not yet committed",
            registry=self.registry)
        # -- fleet-wide KV reuse (admission onboarding) -------------------
        self.kv_onboard = Counter(
            f"{ns}_kv_onboard_total",
            "Prompt blocks the admission path had to source beyond the "
            "local tiers, by source: 'peer' onboarded from another "
            "worker's KV export, 'recompute' left for local prefill "
            "(no peer held them, or every pull failed)",
            ["source"], registry=self.registry)
        self.kv_onboard_bytes = Counter(
            f"{ns}_kv_onboard_bytes_total",
            "KV bytes behind those admission decisions, by source: 'peer' "
            "counts wire bytes pulled, 'recompute' the cache bytes the "
            "local prefill will regenerate",
            ["source"], registry=self.registry)
        for source in ("peer", "recompute"):
            self.kv_onboard.labels(source)
            self.kv_onboard_bytes.labels(source)
        self.prefill_failovers = Counter(
            f"{ns}_prefill_failovers_total",
            "Remote-prefill retries on an alternate prefill instance "
            "after the first one failed, by outcome (ok, failed)",
            ["outcome"], registry=self.registry)
        self.stage = StageMetrics(self.registry)
        self.loop_lag = loop_lag_histogram(self.registry)
        # KVBM tier/prefetch gauges+counters, sampled at scrape time from
        # TieredEngine.kvbm_stats() once attached (zero-valued until then)
        self.kvbm = KvbmStatsCollector(self.registry)
        # decode dispatch taps, sampled at scrape time from the engine's
        # counters once attached (zero-valued until then)
        self.engine = EngineDispatchCollector(self.registry)
        # step flight recorder aggregates (duration/occupancy/gap
        # histograms, pool gauges, compile counters), sampled at scrape
        # time once attached (zero-valued until then)
        self.steptrace = StepTraceCollector(self.registry)

    def attach_tracer(self, tracer) -> None:
        """Observe stage spans finished in this process into the stage
        histogram (idempotent per tracer)."""
        self.stage.attach(tracer)


_metrics: Optional[WorkerMetrics] = None


def get_worker_metrics() -> WorkerMetrics:
    global _metrics
    if _metrics is None:
        _metrics = WorkerMetrics()
    return _metrics


def count_metric(name: str, *labels: str, inc: float = 1) -> None:
    """Best-effort increment of a ``WorkerMetrics`` counter by attribute
    name — accounting must never fail serving, so lookup/label errors are
    swallowed (logged at debug). The one place the try/inc/except shape
    lives, instead of a copy per call site."""
    import logging
    try:
        c = getattr(get_worker_metrics(), name)
        if labels:
            c = c.labels(*labels)
        c.inc(inc)
    except Exception:  # noqa: BLE001 — accounting is never load-bearing
        logging.getLogger(__name__).debug(
            "worker metric %s%r increment failed", name, labels,
            exc_info=True)


__all__ = ["WorkerMetrics", "KvbmStatsCollector", "EngineDispatchCollector",
           "StepTraceCollector", "engine_dispatch_stats",
           "get_worker_metrics", "count_metric"]
