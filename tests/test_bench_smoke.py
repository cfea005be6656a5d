"""``python bench.py --smoke``: the seconds-scale schema run must exit 0
and emit a summary whose every key is populated, so bench regressions
(schema drift, broken phases) surface in tier-1 instead of wasting a
full driver run. No throughput bar is asserted here."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_schema():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # bench measures on ONE device, not the
    # conftest's virtual 8-CPU mesh
    p = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"), "--smoke",
            # regression sentinel rides the same invocation: schema-diffs
            # the fresh summary against the checked-in baseline and fails
            # the run (nonzero exit) on breach
            "--sentinel", os.path.join(REPO, "BENCH_r05.json"),
        ],
        capture_output=True, text=True, timeout=420, cwd=REPO, env=env,
    )
    assert p.returncode == 0, p.stderr[-4000:]
    last = None
    for line in p.stdout.strip().splitlines():
        try:
            last = json.loads(line)
        except ValueError:
            continue
    assert last is not None, p.stdout[-2000:]
    assert last["metric"] == "rag_ingest_embed_index_docs_per_sec"
    s = last["summary"]
    # bench.py's own smoke gate already rejects empty keys; re-assert the
    # load-bearing ones here so the contract lives in the test suite too
    for key in (
        "ingest_mfu_pct", "ingest_roofline", "config4_engine_docs_per_sec",
        "engine_tax_ratio", "engine_stats", "join_e2e_rows_per_sec",
        "wordcount_rows_per_sec", "decoder_tokens_per_sec",
        "knn_recall_at_10", "rerank_p50_ms", "ivf_recall_at_10",
        "ingest_bubbles", "serving", "rerank_cascade_p50_ms",
        "cascade_top8_overlap", "cascade_survivor_rate", "query_qps",
        "query_p50_ms", "query_p95_ms", "query_batch_hist",
        # sustained-window accounting + dual recall + sharded build
        # (ISSUE 4): every phase carries volume and elapsed_s
        "ingest_docs", "ingest_elapsed_s", "ingest_ceiling",
        "config4_default_docs_per_sec", "config4_docs",
        "config4_elapsed_s", "join_rows", "join_elapsed_s",
        "wordcount_rows", "wordcount_elapsed_s", "knn_recall_at_10_f32",
        "sharded_ivf", "mesh_serving",
        # ingest-amortized late-interaction cascade (ISSUE 16): MaxSim
        # cheap stage off the ingest-time token bank + listwise LLM stage
        "maxsim_p50_ms", "maxsim_top8_overlap", "late_bank_build_ms",
        "llm_rerank_overlap",
        # workload-driven autotuner (ISSUE 17): the --tuned arm replays
        # two profiles default-vs-tuned off a validated config
        "tuned_tok_s", "default_tok_s", "tuned",
        # flash prefill (ISSUE 18): tiled online-softmax sweep, flash vs
        # dense at every seq with linear-not-quadratic byte accounting
        "flash_prefill",
        # weight-only int8 (ISSUE 19): fused-dequant serving arm vs full
        # precision — bytes saved off the weights ledger + top-1 agreement
        "weight_quant",
    ):
        assert s.get(key) is not None, key
    # the --tuned arm: both profiles ran both legs, the measured config
    # came out of validation with zero SLO alerts and zero sheds, and
    # the default legs of a chaos-off bench shed nothing either
    tuned = s["tuned"]
    assert tuned["source"] in ("inline_micro_tune", "artifact")
    for pname in ("shared_prefix_chat", "long_doc_rag"):
        tp = tuned["profiles"][pname]
        assert tp["default"] is not None and tp["tuned"] is not None
        assert tp["improvement_x"] is not None
        assert tp["validation_alerts"] == 0
        assert tp["validation_sheds"] == 0
        assert tp["sheds"] == 0
    assert s["tuned_tok_s"] > 0 and s["default_tok_s"] > 0
    assert s["ingest_elapsed_s"] > 0 and s["ingest_docs"] > 0
    # this run is on the CPU, which is not in probes.DEVICE_PEAKS: every
    # utilization must read "not measured", never a share of v5e's peaks
    ceil = s["ingest_ceiling"]
    assert s["ingest_mfu_pct"] == "not measured"
    assert ceil["bound"] == ceil["ceiling_mfu_pct"] == "not measured"
    assert ceil["arith_intensity"] > 0
    assert all(
        row["mfu_pct"] == row["hbm_util_pct"] == "not measured"
        for row in s["ingest_roofline"].values()
    )
    sh = s["sharded_ivf"]
    assert sh.get("error") is None, sh
    assert sh["rows_total"] == sh["shards"] * sh["rows_per_shard"] > 0
    assert 0.0 < sh["recall_at_10"] <= 1.0
    # mesh-sharded serving (PR 14): on this CPU run the arm took its 8
    # devices from a fresh CPU child, emitted the exact single-chip token
    # stream, and the per-device HBM ledger saw every mesh device
    ms = s["mesh_serving"]
    assert ms.get("error") is None, ms
    assert ms["mesh_tok_s"] > 0 and ms["single_chip_tok_s"] > 0
    assert ms["mesh_tokens_match"] is True
    assert ms["mesh"] == {"axes": ["data", "fsdp", "tp"],
                          "shape": [1, 2, 4]}
    mdevs = ms["hbm_device_high_water_bytes"]
    assert set(mdevs) >= {str(i) for i in range(8)}, mdevs
    assert all(v > 0 for v in mdevs.values()), mdevs
    # flash prefill (ISSUE 18): both arms ran at every swept seq, flash
    # emitted the dense greedy tokens, and the byte accounting doubles
    # (not quadruples) per seq doubling — linear, the tentpole claim
    fp = s["flash_prefill"]
    assert fp.get("error") is None, fp
    assert fp["flash_tok_s"] > 0 and fp["dense_tok_s"] > 0
    assert fp["tokens_match"] is True
    assert fp["attn_bytes_linear"] is True
    seqs = [str(x) for x in fp["seqs"]]
    assert set(fp["sweep"]) == set(seqs)
    for a, b in zip(seqs, seqs[1:]):
        fa, fb = (fp["sweep"][a]["attn_bytes_flash"],
                  fp["sweep"][b]["attn_bytes_flash"])
        da, db = (fp["sweep"][a]["attn_bytes_dense"],
                  fp["sweep"][b]["attn_bytes_dense"])
        assert fb <= 3 * fa, (fa, fb)       # linear: ~2x per doubling
        assert db == pytest.approx(4 * da), (da, db)  # dense: quadratic
    # weight-only int8 (ISSUE 19): both arms decoded, the int8 arm's
    # weights ledger footprint shrank >= 1.7x, and its greedy stream
    # agreed with full precision at >= 0.99 top-1
    wq = s["weight_quant"]
    assert wq.get("error") is None, wq
    assert wq["quant_tok_s"] > 0 and wq["base_tok_s"] > 0
    assert wq["weights_hbm_bytes_base"] > wq["weights_hbm_bytes_quant"] > 0
    assert wq["bytes_saved_x"] >= 1.7
    assert wq["agreement"] >= 0.99
    assert 0.0 <= s["knn_recall_at_10_f32"] <= 1.0
    # the query-serving phase ran under load: a survivor rate strictly
    # inside (0, 1] and a non-empty tick batch histogram
    assert 0.0 < s["cascade_survivor_rate"] <= 1.0
    # the MaxSim cheap stage amortizes its encoder work into ingest, so
    # per-query it must beat the truncated-depth encoder cheap stage at
    # the SAME survivor budget; its bank build is a real measurement
    assert 0 < s["maxsim_p50_ms"] < s["rerank_cascade_p50_ms"]
    assert 0.0 <= s["maxsim_top8_overlap"] <= 1.0
    assert s["late_bank_build_ms"] > 0
    # the listwise LLM stage rode the continuous serve path; random-init
    # weights emit no parseable permutation, so the malformed-window
    # fallback must keep the candidate set intact (permutation, no loss)
    assert s["llm_rerank_overlap"] >= 0.9
    assert s["query_batch_hist"]
    assert s["query_qps"] > 0
    bub = s["ingest_bubbles"]
    assert set(bub["pct"]) >= {"tokenize", "h2d", "dispatch", "compute"}
    # stage percentages + device-compute residual account for the wall
    # (> 100 is legal — it means host stages overlapped device compute)
    assert sum(bub["pct"].values()) == pytest.approx(100.0, abs=2.0) or \
        bub["sum_host_pct"] > 100.0
    srv = s["serving"]
    for key in (
        "throughput_x", "p50_x", "occupancy", "static_tok_s",
        "continuous_tok_s", "measured_path", "direct_api_throughput_x",
        "direct_api_p50_x", "prefix_hit_rate", "prefill_tokens_saved",
        "ttft_p50_ms", "spec_acceptance_rate", "tokens_per_dispatch",
        "spec_tok_s", "plain_tok_s", "spec_speedup_x", "kv_quant_tok_s",
        "kv_bytes_saved",
        # registry-sourced latency keys (PR 7): bench re-reads these from
        # the MetricsRegistry histograms, same series /metrics scrapes
        "queue_wait_p50_ms", "tpot_p50_ms", "e2e_p50_ms",
        # fault-tolerance accounting (PR 10): a clean smoke run reports
        # zero sheds/restarts and a quiescent degradation ladder
        "requests_shed", "restarts", "degradation_level",
        # paged KV trace (PR 11): both arms' throughput, both gauges,
        # and the fixed-HBM admissibility comparison
        "kv_fragmentation", "kv_fragmentation_dense", "paged_tok_s",
        "dense_tok_s", "paged_max_slots", "dense_max_slots",
        "paged_tokens_match",
        # replicated fleet (PR 12): throughput/p95/hit-rate off the
        # 2-replica affinity-routed arm + the chaos failover verdict
        "fleet_tok_s", "fleet_p95_ms", "fleet_prefix_hit_rate",
        "fleet_hit_ratio", "fleet_chaos_p95_ms", "fleet_failover_ok",
        # disaggregated lanes + two-tier cache + admission scheduler
        # (PR 13): the bursty decode-tail pair, lane-edge migration
        # accounting, the churny tier-2 trace, and the preemption phase
        "disagg_decode_p95_ms", "interleaved_decode_p95_ms",
        "disagg_tokens_match", "kv_migrated_blocks",
        "prefix_hit_rate_t2", "t2_recovered_prefill_tokens",
        "t2_tokens_match", "preemptions_total", "preempt_sheds",
        "preempt_tokens_match",
    ):
        assert srv.get(key) is not None, key
    # span-derived latencies are real measurements off the decode phase
    assert srv["e2e_p50_ms"] > 0
    assert srv["tpot_p50_ms"] > 0
    assert srv["queue_wait_p50_ms"] >= 0
    # e2e covers queue wait + generation, so it bounds both from above
    assert srv["e2e_p50_ms"] >= srv["tpot_p50_ms"]
    assert 0.0 < srv["occupancy"] <= 1.0
    # the serving headline must come off the product path, not the bare
    # model API
    assert "pw_ai_answer" in srv["measured_path"]
    # chaos is off in the smoke run, so nothing may shed, restart, or
    # climb the degradation ladder (the sentinel enforces the same)
    assert srv["requests_shed"] == 0
    assert srv["restarts"] == 0
    assert srv["degradation_level"] == 0
    # the fleet arm: affinity routing held the single-replica prefix hit
    # rate, and the chaos-on-one-replica trace reached terminal answers
    assert srv["fleet_hit_ratio"] >= 0.9
    assert srv["fleet_failover_ok"] is True
    assert 0.0 < srv["fleet_prefix_hit_rate"] <= 1.0
    assert srv["fleet_tok_s"] > 0
    # the shared-prefix trace actually exercised the KV prefix cache
    assert 0.0 < srv["prefix_hit_rate"] <= 1.0
    assert srv["prefill_tokens_saved"] > 0
    assert srv["ttft_p50_ms"] > 0
    # the speculative-decode trace: the shallow draft must agree with the
    # full model well above chance, and every verify dispatch must have
    # amortised over more than 1.5 emitted tokens on the shared-head trace
    assert srv["spec_acceptance_rate"] > 0.3
    assert srv["tokens_per_dispatch"] > 1.5
    assert srv["spec_tok_s"] > 0 and srv["plain_tok_s"] > 0
    assert srv["kv_quant_tok_s"] > 0
    # the int8 arm actually shrank the KV footprint
    assert srv["kv_bytes_saved"] > 0
    # the paged-KV trace: identical greedy tokens across arms, a
    # fragmentation gauge strictly below the dense pool's, and strictly
    # more admissible slots at the same HBM budget
    assert srv["paged_tokens_match"]
    assert 0.0 <= srv["kv_fragmentation"] <= 1.0
    assert 0.0 <= srv["kv_fragmentation_dense"] <= 1.0
    assert srv["kv_fragmentation"] < srv["kv_fragmentation_dense"]
    assert srv["paged_tok_s"] > 0 and srv["dense_tok_s"] > 0
    assert srv["paged_max_slots"] > srv["dense_max_slots"] > 0
    # disaggregated lanes (PR 13): on the bursty mixed trace the decode
    # tail must not regress vs interleaved admission, lane scheduling
    # must not change a greedy token, and the prefill->decode lane edge
    # actually handed blocks over
    assert srv["disagg_decode_p95_ms"] <= srv["interleaved_decode_p95_ms"]
    assert srv["disagg_tokens_match"] is True
    assert srv["kv_migrated_blocks"] > 0
    # two-tier prefix cache: the churny trace actually hit the host tier
    # and promoted blocks back to the device; the t2-off (budget 0) arm
    # is byte-identical
    assert srv["prefix_hit_rate_t2"] > 0
    assert srv["t2_recovered_prefill_tokens"] > 0
    assert srv["t2_tokens_match"] is True
    # admission scheduler: the over-budget construction preempted (slot
    # rewound, KV parked, request requeued) — never shed — and the
    # re-decoded stream is byte-identical to an unscheduled server
    assert srv["preemptions_total"] >= 1
    assert srv["preempt_sheds"] == 0
    assert srv["preempt_tokens_match"] is True
    # pipeline-depth observability (PR 9): per-operator latency telemetry
    # sampled during the streaming phases, the HBM ledger saw the decoder
    # pools, and the SLO watchdog state rode the summary out
    eng = s["engine"]
    assert eng["op_latency_p50_ms"] > 0
    assert eng["operators"] > 0
    assert s["hbm_high_water_bytes"] > 0
    comps = s["hbm_components"]
    # dense servers report slot_pool; the paged-arm servers report the
    # global block pool + table (either proves the ledger saw a pool)
    assert comps.get("slot_pool", 0) > 0 or comps.get("kv_blocks", 0) > 0, \
        comps
    assert comps.get("kv_blocks", 0) > 0 and \
        comps.get("block_table", 0) > 0, comps
    # the late-interaction token bank is device-resident and on the ledger
    assert comps.get("late_bank", 0) > 0, comps
    slo = s["slo"]
    assert slo["breaches"] == 0 and slo["alerting"] == []
    assert slo["enabled"] in (True, False)
