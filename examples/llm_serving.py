"""LLM serving the way a PaddleNLP deployment user writes it
(reference pattern: ``PaddleNLP/llm/predict/predictor.py`` over
AnalysisPredictor): finetune a tiny Qwen2 on a deterministic task, then
serve it three ways —
1. ``GenerationPredictor`` with a LEFT-PADDED variable-length batch
   (each row's continuation must match its unpadded generation),
2. beam search with a length penalty,
3. an AOT-exported decode artifact (``export_generation``) replayed via
   ``load_generation`` — the deployable unit,
4. the continuous-batching ``ServingEngine`` with a SHARED SYSTEM
   PROMPT: the prefix cache prefills it once, every later request maps
   its blocks (prefix hit rate > 0) and must produce the exact tokens
   the cold path would,
5. TENSOR-PARALLEL serving (``tp_degree=2`` when >= 2 devices are
   visible): the same engine sharded over an ``mp`` mesh axis — KV
   pool split on kv_heads, one logits all_gather per step — must
   produce the exact tokens the single-device engine did,
6. RAGGED mixed-batch serving: one executable per engine, kill-switch
   parity asserted,
7. a dropless Qwen2-MoE through the SAME engine: served greedy tokens
   must equal ``generate(cache_impl="dense")``'s, with decode-time
   routing telemetry flowing,
8. (int8 KV cache: half the KV bytes per decode step at a >= 0.99
   token match rate),
9. REQUEST TRACING + SLO GOODPUT: serve a concurrent-admission wave,
   dump a Perfetto-loadable Chrome trace of the request lifecycles,
   print the engine's always-on TTFT/ITL p99 digests, and measure
   goodput under SLO with the closed-loop load generator,
10. ENGINE REPLICATION + DISAGGREGATED PREFILL: two replicas behind
    the session-affine router (token-exact vs one engine, affinity
    hits on a second turn), then a dedicated prefill engine streaming
    finished KV blocks into the decode replica's pool — still
    token-exact.

    python examples/llm_serving.py --tiny
"""
import argparse
import os
import tempfile

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.generation import GenerationConfig, load_generation
from paddle_tpu.inference import create_generation_predictor
from paddle_tpu.models.qwen2 import Qwen2Config, Qwen2ForCausalLM


def _train_chain(model, vocab, steps, lr=3e-3):
    """Teach ids[t+1] = (ids[t]*5+3) % vocab."""
    from paddle_tpu.jit import TrainStep
    opt = paddle.optimizer.AdamW(lr, parameters=model.parameters())
    step = TrainStep(model, lambda out, a, k: out, opt)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        start = rng.randint(0, vocab, (16, 1))
        rows = [start]
        for _ in range(24):
            rows.append((rows[-1] * 5 + 3) % vocab)
        ids = np.concatenate(rows, 1).astype(np.int64)
        x = paddle.to_tensor(ids[:, :-1])
        y = paddle.to_tensor(ids[:, 1:])
        losses.append(float(step(x, labels=y).numpy()))
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=120)
    args = ap.parse_args(argv)

    vocab = 64 if args.tiny else 32000
    cfg = Qwen2Config.tiny(vocab=vocab, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=176) \
        if args.tiny else Qwen2Config()
    paddle.seed(17)
    model = Qwen2ForCausalLM(cfg)
    model.train()
    losses = _train_chain(model, vocab, args.steps)
    print(f"finetune loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    model.eval()

    def chain(x, n):
        out = []
        for _ in range(n):
            x = (x * 5 + 3) % vocab
            out.append(x)
        return out

    # ---- 1. left-padded variable-length batch through the predictor
    pred = create_generation_predictor(
        model, GenerationConfig(max_new_tokens=6, pad_token_id=0))
    p_short = [7, chain(7, 1)[0]]
    p_long = [11] + chain(11, 3)
    padded = np.asarray([[0, 0] + p_short, p_long], np.int64)
    mask = np.asarray([[0, 0, 1, 1], [1, 1, 1, 1]], np.int64)
    batch_out = pred.generate(padded,
                              attention_mask=paddle.to_tensor(mask))
    want_s = chain(p_short[-1], 6)
    want_l = chain(p_long[-1], 6)
    n_ok = int((batch_out[0] == want_s).sum()) + \
        int((batch_out[1] == want_l).sum())
    print(f"left-padded batch: {n_ok}/12 tokens follow the chain")

    # ---- 2. beam search with a length penalty
    beam_out, beam_score = model.generate(
        paddle.to_tensor(np.asarray([p_long], np.int64)),
        max_new_tokens=6, decode_strategy="beam_search", num_beams=4,
        length_penalty=0.6)
    print("beam-4:", beam_out.numpy()[0].tolist(),
          f"score {float(beam_score.numpy()[0]):.3f}")

    # ---- 3. AOT export + replay (the deployable artifact)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "serving")
        model.export_generation(
            path, batch_size=1, prompt_len=len(p_long),
            max_new_tokens=6,
            generation_config=GenerationConfig(
                decode_strategy="beam_search", num_beams=4,
                length_penalty=0.6))
        loaded = load_generation(path)
        replay = loaded(np.asarray([p_long], np.int64))
        assert replay.tolist() == beam_out.numpy().tolist(), \
            "AOT replay diverged from live beam search"
        print("AOT artifact replay matches live beam search")

    # ---- 4. continuous-batching engine + shared system prompt
    from paddle_tpu.inference import ServingConfig, ServingEngine
    system_prompt = np.asarray(chain(23, 24), np.int64)  # shared header
    users = [[7] + chain(7, 2), [11, 19], [3] + chain(3, 3)]
    prompts = [np.concatenate([system_prompt, u]) for u in users]

    def serve(enable_cache):
        eng = ServingEngine(model, ServingConfig(
            num_slots=2, block_size=8, max_model_len=96,
            prefill_chunk=16, enable_prefix_cache=enable_cache))
        outs = eng.serve(list(prompts), max_new_tokens=6)
        # a second wave hits the retired requests' published blocks
        outs += eng.serve(list(prompts), max_new_tokens=6)
        st = eng.stats()
        eng.shutdown()                 # allocator leak sweep
        return outs, st

    warm, st = serve(True)
    cold, _ = serve(False)
    for a, b in zip(warm, cold):
        assert a.tolist() == b.tolist(), \
            "prefix caching changed the served tokens"
    print(f"serving engine: prefix hit rate "
          f"{st['prefix_hit_rate']:.2f} over {len(warm)} requests, "
          f"{st['prefill_chunks']} prefill chunks with "
          f"{st['prefill_compiles']} compile(s); tokens exact vs "
          f"cold cache")

    # ---- 5. tensor-parallel serving (needs >= 2 devices)
    import jax
    if len(jax.devices()) >= 2:
        eng = ServingEngine(model, ServingConfig(
            num_slots=2, block_size=8, max_model_len=96,
            prefill_chunk=16, tp_degree=2))
        tp_outs = eng.serve(list(prompts), max_new_tokens=6)
        st_tp = eng.stats()
        # census is empty on very old jax (no jit().trace) — degrade
        census = eng.collective_census().get("decode", [])
        eng.shutdown()
        for a, b in zip(tp_outs, warm[:len(tp_outs)]):
            assert a.tolist() == b.tolist(), \
                "tensor parallelism changed the served tokens"
        gathers = [r for r in census if r["op"] == "all_gather"]
        n_gather = gathers[0]["count"] if gathers else 0
        print(f"tensor-parallel engine: tp={st_tp['tp_degree']}, "
              f"{n_gather} logits all_gather/step "
              f"({st_tp['tp_collective_bytes_per_step']}B), pool "
              f"{st_tp['tp_pool_bytes_per_shard']}B/shard; tokens "
              f"exact vs single-device")
    else:
        print("tensor-parallel engine: skipped (1 device visible; "
              "run under a multi-chip/8-CPU-device mesh)")

    # ---- 6. ragged mixed-batch serving: ONE executable per engine
    # The engines above already ran the ragged step: decode rows,
    # verify windows and prefill chunks ride ONE compiled launch per
    # tick. Pin the collapse on a fresh engine.
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=16))
    ragged_outs = eng.serve(list(prompts), max_new_tokens=6)
    st_ragged = eng.stats()
    eng.shutdown()
    assert st_ragged["executables_compiled"] == 1 and \
        st_ragged["prefill_compiles"] == 0, st_ragged
    for a, b in zip(ragged_outs, cold):
        assert a.tolist() == b.tolist(), \
            "ragged mixed batch changed the served tokens"
    print(f"ragged mixed-batch engine: "
          f"{st_ragged['executables_compiled']} executable, "
          f"{st_ragged['prefill_chunks']} prefill chunks inside it; "
          f"tokens exact vs the cold-cache engine")

    # ---- 7. MoE serving: a dropless Qwen2-MoE through the SAME engine
    # Attention is vanilla GQA (the paged/ragged kernels run
    # unmodified); dropless routing is per-row, so the packed ragged
    # rows of other requests cannot perturb a row's experts — served
    # greedy tokens must equal the dense cached forward's, and the
    # decode-time routing telemetry must flow.
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)
    paddle.seed(7)
    moe_cfg = Qwen2MoeConfig.tiny(vocab=vocab, hidden=64, layers=2,
                                  heads=4, kv_heads=2, moe_ffn=32,
                                  shared_ffn=64, experts=4, topk=2)
    moe_cfg.dropless = True              # capacity routing is rejected
    moe = Qwen2MoeForCausalLM(moe_cfg)
    _train_chain(moe, vocab, max(args.steps // 4, 20))
    moe.eval()
    moe_prompts = [np.asarray(chain(5, 4), np.int64),
                   np.asarray(chain(9, 6), np.int64)]
    dense_refs = []
    for p in moe_prompts:
        out, _ = moe.generate(paddle.to_tensor(p[None]),
                              max_new_tokens=6, cache_impl="dense",
                              decode_strategy="greedy_search")
        dense_refs.append(np.asarray(out.numpy())[0])
    eng = ServingEngine(moe, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=16))
    moe_outs = eng.serve([p.astype(np.int32) for p in moe_prompts],
                         max_new_tokens=6)
    st_moe = eng.stats()
    eng.shutdown()
    for served, ref in zip(moe_outs, dense_refs):
        assert served.tolist() == ref.tolist(), \
            "MoE serving diverged from the dense cached forward"
    assert st_moe["moe"] and st_moe["moe_dispatches"] > 0
    print(f"MoE engine: served == dense tokens; routing entropy "
          f"{st_moe['moe_routing_entropy']:.2f} over "
          f"{st_moe['moe_dispatches']} dispatches, "
          f"{st_moe['executables_compiled']} executable")

    # ---- 8. int8 KV cache: half the KV bytes per decode step
    # The block pool stores int8 K/V + per-(block, position, head)
    # absmax scales; kernels dequantize in VMEM after the block load.
    # Quantization perturbs logits, so int8-vs-fp is a token MATCH
    # RATE budget (>= 0.99 on the serving bench; a trained chain model
    # should be exact) — while pool bytes and KV bytes/step halve.
    kv_prompts = [np.asarray([7] + chain(7, n), np.int32)
                  for n in (3, 9, 5)]
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96,
        prefill_chunk=16))
    fp_outs = eng.serve(list(kv_prompts), max_new_tokens=6)
    st_fp = eng.stats()
    eng.shutdown()
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96,
        prefill_chunk=16, kv_cache_dtype="int8"))
    q8_outs = eng.serve(list(kv_prompts), max_new_tokens=6)
    st_q8 = eng.stats()
    eng.shutdown()
    tot = sum(len(a) for a in fp_outs)
    hit = sum(int((np.asarray(a) == np.asarray(b)).sum())
              for a, b in zip(fp_outs, q8_outs))
    match = hit / tot
    assert match >= 0.99, \
        f"int8 KV match rate {match:.3f} below the 0.99 budget"
    assert st_q8["kv_pool_bytes"] < 0.6 * st_fp["kv_pool_bytes"]
    print(f"int8 KV cache: match rate {match:.2f} vs fp, pool "
          f"{st_q8['kv_pool_bytes']}B vs {st_fp['kv_pool_bytes']}B "
          f"({st_q8['kv_pool_bytes'] / st_fp['kv_pool_bytes']:.2f}x), "
          f"KV bytes/step {st_q8['kv_bytes_per_step']} vs "
          f"{st_fp['kv_bytes_per_step']}")

    # ---- 9. request tracing + SLO goodput
    # Serve a wave with CONCURRENT admission (requests arrive while
    # earlier ones decode), dump the Chrome trace — open it at
    # https://ui.perfetto.dev: per-slot request timelines, per-tick
    # engine spans — and measure goodput under SLO with the
    # closed-loop load generator. The TTFT/ITL digests are always on
    # (P², bounded memory); tracing's kill switch is PADDLE_TPU_TRACE=0.
    from paddle_tpu.inference.loadgen import SLO, run_load
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=16))
    eng.serve([prompts[0]], max_new_tokens=2)          # warm/compile
    wave = [np.concatenate([system_prompt, u]).astype(np.int32)
            for u in users] * 2
    report = run_load(eng, wave, qps=50.0, mode="open",
                      max_new_tokens=6,
                      slo=SLO(ttft_ms=2000.0, itl_ms=500.0))
    st9 = eng.stats()
    assert st9["ttft_ms"]["count"] > 0 and st9["itl_ms"]["count"] > 0
    trace_path = eng.dump_trace(os.path.join(
        tempfile.gettempdir(), "paddle_tpu_serve_trace.json"))
    eng.shutdown()
    print(f"tracing + goodput: {report['completed']}/"
          f"{report['requests']} requests, goodput "
          f"{report['goodput']:.2f} at {report['offered_qps']} QPS "
          f"(TTFT p99 {report['ttft_p99_ms']:.1f} ms, ITL p99 "
          f"{report['itl_p99_ms']:.1f} ms); engine digests: TTFT p99 "
          f"{st9['ttft_ms']['p99']:.1f} ms, ITL p99 "
          f"{st9['itl_ms']['p99']:.1f} ms over "
          f"{st9['trace_events']} trace events -> {trace_path}")

    # ---- 10. engine replication + disaggregated prefill -> decode
    # Two routed replicas: a session's second turn lands on the
    # replica that published its first turn's blocks (the router and
    # admission share ONE prompt->hash walk), token-exact vs a single
    # engine. Then a disaggregated cluster: a dedicated prefill engine
    # streams each finished prompt's KV blocks into the decode
    # replica's pool — still token-exact. Kill switch:
    # PADDLE_TPU_CLUSTER=0 (one plain engine behind the cluster API).
    from paddle_tpu.inference import ClusterConfig, EngineCluster
    ref_eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96,
        prefill_chunk=16))
    ref10 = ref_eng.serve(list(prompts), max_new_tokens=6)
    ref_eng.shutdown()
    cluster = EngineCluster(
        model, ClusterConfig(num_replicas=2),
        ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                      prefill_chunk=16))
    got10 = cluster.serve(list(prompts), max_new_tokens=6)
    # turn 2 of "session 0": same prompt + a tail -> affine route
    turn2 = np.concatenate([prompts[0], got10[0][:2]])
    cluster.serve([turn2], max_new_tokens=4)
    stc = cluster.stats()
    for a, b in zip(got10, ref10):
        assert a.tolist() == b.tolist(), \
            "cluster diverged from the single engine"
    assert stc["router_affinity_hits"] >= 1
    cluster.shutdown()
    disagg = EngineCluster(
        model, ClusterConfig(num_replicas=1, prefill_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                      prefill_chunk=16))
    got10d = disagg.serve(list(prompts), max_new_tokens=6)
    std = disagg.stats()
    for a, b in zip(got10d, ref10):
        assert a.tolist() == b.tolist(), \
            "disaggregated prefill->decode diverged from colocated"
    assert std["kv_blocks_transferred"] > 0
    disagg.shutdown()
    print(f"cluster: N=2 token-exact, affinity hits "
          f"{stc['router_affinity_hits']} (hit rate "
          f"{stc['router_affinity_hit_rate']:.2f}); disaggregated "
          f"token-exact with {std['kv_blocks_transferred']} KV "
          f"blocks streamed prefill->decode")

    # ---- 11. mega-kernelized decode tick + per-request sampling
    # Fused norm->QKV / attention->O-proj / MLP boundaries inside the
    # one ragged executable (kill switch PADDLE_TPU_FUSED_DECODE=0,
    # token-exact vs unfused — off TPU the fallback IS the unfused
    # graph bit-for-bit), kernel census measured per engine, and the
    # per-slot sampling head: two requests with DIFFERENT sampling
    # knobs ride one batch and one executable — a top_k=1 row
    # reproduces the greedy chain while its neighbor samples hot.
    os.environ["PADDLE_TPU_FUSED_DECODE"] = "0"
    eng_uf = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=16))
    ref11 = eng_uf.serve(list(prompts), max_new_tokens=6)
    eng_uf.shutdown()
    del os.environ["PADDLE_TPU_FUSED_DECODE"]
    eng_f = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=16))
    got11 = eng_f.serve(list(prompts), max_new_tokens=6)
    st11 = eng_f.stats()
    for a, b in zip(got11, ref11):
        assert a.tolist() == b.tolist(), "fused tick diverged"
    assert st11["fused_decode"] and st11["kernels_per_tick"] > 0
    eng_f.shutdown()
    eng_s = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=16,
        decode_strategy="sampling", temperature=1.5, seed=9))
    rid_cold = eng_s.submit(prompts[0], 6, temperature=1e-6, top_k=1)
    rid_hot = eng_s.submit(prompts[1], 6, temperature=1.3, top_p=0.9)
    done11 = eng_s.run()
    st11s = eng_s.stats()
    assert done11[rid_cold].tolist() == ref11[0].tolist(), \
        "per-request top_k=1 row must reproduce the greedy chain"
    assert st11s["executables_compiled"] == 1, \
        "distinct sampling configs must share ONE executable"
    eng_s.shutdown()
    print(f"fused decode tick: token-exact vs unfused, "
          f"kernels_per_tick {st11['kernels_per_tick']} (launch proxy "
          f"{st11['kernel_launch_proxy_per_tick']}); per-request "
          f"sampling: greedy row exact next to a hot row, "
          f"{st11s['executables_compiled']} executable")

    # ---- 12. SLO-aware preemptive scheduling + host-DRAM KV tier
    # A low-priority long request streams a few tokens, then two
    # high-priority requests arrive: the scheduler preempts the long
    # (its live KV blocks spill to the host-DRAM tier), serves the
    # high class FIRST, and resumes the victim token-exact — its full
    # stream matches the never-preempted reference bit-for-bit.
    eng_ref = ServingEngine(model, ServingConfig(
        num_slots=4, block_size=8, max_model_len=96,
        prefill_chunk=16))
    ref12 = eng_ref.serve(list(prompts), max_new_tokens=6)
    eng_ref.shutdown()
    stream_events = []
    eng_p = ServingEngine(
        model, ServingConfig(num_slots=2, block_size=8,
                             max_model_len=96, prefill_chunk=16),
        stream_callback=lambda rid, tok: stream_events.append(rid))
    rid_lo12 = eng_p.submit(prompts[0], 6, priority=0)
    for _ in range(3):
        eng_p.step()                 # the long streams a few tokens
    rid_a = eng_p.submit(prompts[1], 6, priority=2)
    rid_b = eng_p.submit(prompts[2], 6, priority=2)
    done12 = eng_p.run()
    st12 = eng_p.stats()
    for rid, want in zip((rid_lo12, rid_a, rid_b), ref12):
        assert done12[rid].tolist() == want.tolist(), \
            "preempted/resumed stream diverged from never-preempted"
    assert st12["preemptions"] >= 1 and st12["kv_blocks_spilled"] >= 1
    # the high class CUT IN: both hi requests delivered their first
    # token while the preempted low request still had tokens to stream
    lo_last = len(stream_events) - 1 - stream_events[::-1].index(
        rid_lo12)
    assert stream_events.index(rid_a) < lo_last
    assert stream_events.index(rid_b) < lo_last
    eng_p.shutdown()
    print(f"preemptive scheduling: {st12['preemptions']} preemption, "
          f"{st12['kv_blocks_spilled']} blocks spilled to host / "
          f"{st12['kv_blocks_restored']} restored "
          f"({st12['preempt_swap_resumes']} swap, "
          f"{st12['preempt_recompute_resumes']} recompute resumes); "
          f"resumed stream token-exact vs never-preempted")

    # ---- 13. Fleet flight recorder: one merged Perfetto trace +
    # per-tick roofline attribution. A disaggregated cluster (1
    # prefill + 1 decode replica) serves a few requests; the merged
    # trace shows one pid per replica, the router lane, and each
    # request's prefill -> handoff (flow arrow) -> decode spans under
    # ONE cluster-global rid; stats()['roofline'] attributes where
    # each tick's time went (MFU / HBM-BW per executable).
    from paddle_tpu.inference.cluster import (ClusterConfig,
                                              EngineCluster)
    cl = EngineCluster(
        model, ClusterConfig(num_replicas=1, prefill_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                      prefill_chunk=16))
    rids13 = [cl.submit(p, 5) for p in prompts]
    done13 = cl.run()
    assert sorted(done13) == sorted(rids13)
    doc = cl.export_trace()
    procs = {e["pid"]: e["args"]["name"]
             for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"replica0:decode", "replica1:prefill",
            "EngineCluster"} <= set(procs.values())
    flows_s = {e["id"] for e in doc["traceEvents"]
               if e.get("ph") == "s"}
    flows_f = {e["id"] for e in doc["traceEvents"]
               if e.get("ph") == "f"}
    assert flows_s and flows_s == flows_f, \
        "every handoff flow start must resolve to a finish"
    g = rids13[0]
    req_pids = {e["pid"] for e in doc["traceEvents"]
                if e.get("name") == f"req{g}" and e.get("ph") == "X"}
    assert len(req_pids) == 2, \
        "one global rid must span prefill AND decode pids"
    roof = cl.stats()["roofline"]
    on_chip = roof["device"] != "cpu"   # utilizations need a chip peak
    assert (roof["step_mfu"] is not None) == on_chip
    with tempfile.TemporaryDirectory() as d13:
        cl.export_trace(os.path.join(d13, "fleet.json"))
    cl.shutdown()
    print(f"flight recorder: merged trace spans {len(procs)} pids, "
          f"{len(flows_s)} handoff flow links resolved, req{g} "
          f"end-to-end across 2 replicas; roofline on "
          f"{roof['device']}: step_mfu {roof['step_mfu']}, "
          f"hbm_bw_util {roof['step_hbm_bw_util']}")

    # ---- 14. TREE speculation at the same verify node budget. Two
    # claims. Safety rail first: a chain-topology tree
    # (spec_tree=(0,1,2)) IS the linear gamma=3 engine — identical
    # greedy tokens on the chain-task model. Then the win: on a model
    # trained on a BRANCHING corpus (every token has a 0.6-majority
    # and 0.4-minority successor), sampled verify takes the minority
    # fork 40% of the time; a linear chain stalls there while a tree
    # spending one of the same 5 nodes on the sibling fork covers
    # both successors — mean accepted length strictly higher.
    scfg14 = dict(num_slots=2, block_size=8, max_model_len=96,
                  num_speculative_tokens=3)
    prompts14 = [np.asarray([7] + chain(7, 4), np.int64),
                 np.asarray([11] + chain(11, 7), np.int64)]
    eng_lin = ServingEngine(model, ServingConfig(**scfg14))
    ref14 = eng_lin.serve([p.copy() for p in prompts14],
                          max_new_tokens=8)
    eng_lin.shutdown()
    eng_tree = ServingEngine(model, ServingConfig(
        spec_tree=(0, 1, 2), **scfg14))
    out14 = eng_tree.serve([p.copy() for p in prompts14],
                           max_new_tokens=8)
    st14 = eng_tree.stats()
    eng_tree.shutdown()
    assert [o.tolist() for o in out14] == [o.tolist() for o in ref14], \
        "chain-topology tree diverged from the linear engine"
    assert st14["spec_tree_nodes"] == 4
    print(f"tree spec (chain topology): token-exact vs linear, "
          f"{st14['spec_tree_nodes']} verify nodes, accepted-len "
          f"p50 {st14['spec_accept_len']['p50']:.1f}")

    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    v14 = 12
    crng = np.random.RandomState(0)
    succ1 = crng.permutation(v14)
    succ2 = (succ1 + 1 + crng.randint(0, v14 - 1, v14)) % v14

    def markov(n, r):
        t = r.randint(v14)
        out = [t]
        for _ in range(n - 1):
            t = int(succ1[t]) if r.rand() < 0.6 else int(succ2[t])
            out.append(t)
        return np.array(out, np.int64)

    paddle.seed(11)
    np.random.seed(11)
    branchy = LlamaForCausalLM(LlamaConfig(
        vocab_size=v14, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=256))
    opt14 = paddle.optimizer.Adam(5e-3,
                                  parameters=branchy.parameters())
    trng = np.random.RandomState(1)
    for _ in range(35):
        b = np.stack([markov(49, trng) for _ in range(12)])
        loss14 = branchy(paddle.to_tensor(b[:, :-1]),
                         labels=paddle.to_tensor(b[:, 1:]))
        opt14.clear_grad()
        loss14.backward()
        opt14.step()
    branchy.eval()
    mprompts = [markov(48, np.random.RandomState(100 + i))
                for i in range(6)]

    def accept_len(spec_tree):
        eng = ServingEngine(branchy, ServingConfig(
            num_slots=3, block_size=16, max_model_len=128,
            max_new_tokens=24, num_speculative_tokens=4,
            decode_strategy="sampling", temperature=1.0, seed=5,
            spec_ngram_max=1, spec_tree=spec_tree))
        eng.serve([p.copy() for p in mprompts])
        st = eng.stats()
        eng.shutdown()
        return st["spec_mean_accepted_len"]

    al_lin = accept_len(None)
    al_tree = accept_len((0, 0, 1, 3))
    assert al_tree > al_lin, (al_tree, al_lin)
    print(f"tree spec (branching corpus, sampled, 5-node budget): "
          f"accepted len {al_tree:.2f} vs linear {al_lin:.2f} "
          f"(+{al_tree - al_lin:.2f} tokens per verify window)")

    # ---- 15. fleet health engine: alerts + incident capture ---------
    # Healthy arm: generous SLOs (first-wave TTFT includes the compile
    # on CPU) — the false-positive pin: a clean serve fires NOTHING.
    scfg15 = dict(num_slots=3, block_size=16, max_model_len=128,
                  max_new_tokens=16)
    eng_ok = ServingEngine(branchy, ServingConfig(
        health_slo_ttft_ms=600000.0, health_slo_itl_ms=600000.0,
        **scfg15))
    eng_ok.serve([p.copy() for p in mprompts])
    st_ok = eng_ok.stats()
    h_ok = eng_ok.health()
    eng_ok.shutdown()
    assert st_ok["health_score"] == 1.0 and st_ok["alerts_firing"] == 0
    assert st_ok["alerts_fired_total"] == 0
    assert h_ok["alerts_firing"] == []
    print(f"health (steady state): score "
          f"{st_ok['health_score']:.2f}, alerts fired "
          f"{st_ok['alerts_fired_total']} (false-positive pin holds)")

    # Overload arm: an unmeetable SLO burns the error budget at ~100x
    # in both burn windows — the fast-burn alert pages and an incident
    # bundle (manifest + stats + journal) lands on disk, atomically.
    with tempfile.TemporaryDirectory() as inc_dir:
        os.environ["PADDLE_TPU_INCIDENT_DIR"] = inc_dir
        try:
            eng_bad = ServingEngine(branchy, ServingConfig(
                health_slo_ttft_ms=1e-3, health_slo_itl_ms=1e-3,
                health_burn_fast_s=0.5, health_burn_slow_s=2.0,
                health_burn_min_requests=2, **scfg15))
            eng_bad.serve([p.copy() for p in mprompts])
            st_bad = eng_bad.stats()
            h_bad = eng_bad.health()
            eng_bad.shutdown()
        finally:
            del os.environ["PADDLE_TPU_INCIDENT_DIR"]
        assert st_bad["alerts_fired_total"] > 0
        fired15 = {e["alert"] for e in h_bad["journal"]}
        assert "slo_fast_burn" in fired15, fired15
        bundles = sorted(d for d in os.listdir(inc_dir)
                         if d.startswith("incident-"))
        assert bundles, "overload fired but captured no incident"
        import json as _json
        man = _json.load(open(os.path.join(
            inc_dir, bundles[0], "manifest.json")))
        snap = _json.load(open(os.path.join(
            inc_dir, bundles[0], "stats.json")))
        assert man["alert"] in fired15 and "roofline" in snap
        print(f"health (overload): burn fast "
              f"{h_bad['burn_rate']['fast']:.0f}x budget, fired "
              f"{sorted(fired15)}, incident bundle "
              f"{bundles[0]} (manifest+stats+journal loadable)")

    # ---- 16. batched multi-LoRA serving: two TRAINED tenants, one tick
    # Each tenant fine-tunes ONLY the attention projections of a copy
    # of the served base model on its own arithmetic chain, then ships
    # the weight DELTA as rank-r SVD factors of W_tuned - W_base —
    # exactly what a LoRA checkpoint is, produced without any extra
    # training machinery. ONE engine then serves both tenants plus a
    # base-model rider in the SAME ragged tick: per-tenant outputs
    # must be distinct (the tenants learned different rules) and
    # token-exact vs a solo run of each adapter. Rank equals hidden
    # here so the factors carry the delta exactly — a tiny-model
    # concession (at hidden=64 any truncation drops ~half the delta's
    # energy) that keeps the demo deterministic; real checkpoints
    # ship r << d.
    lora_rank = cfg.hidden_size
    base_sd = {k: np.asarray(v.numpy()).copy()
               for k, v in model.state_dict().items()}
    attn_leafs = ("q_proj", "k_proj", "v_proj", "o_proj")

    def train_adapter(mul, add, steps):
        """Fine-tune a base-model copy (attention projections only)
        on ids[t+1] = (ids[t]*mul+add) % vocab, return the rank-r
        SVD adapter {qualified_name: (A, B)} of the weight delta."""
        paddle.seed(23)
        tuned = Qwen2ForCausalLM(cfg)
        tuned.set_state_dict(base_sd)
        tuned.train()
        attn_ws = []
        for name, p in tuned.named_parameters():
            if (name.rsplit(".", 1)[-1] == "weight"
                    and name.split(".")[-2] in attn_leafs):
                attn_ws.append(p)
            else:
                p.stop_gradient = True   # freeze everything else
        from paddle_tpu.jit import TrainStep
        opt = paddle.optimizer.AdamW(1e-2, parameters=attn_ws)
        step = TrainStep(tuned, lambda out, a, k: out, opt)
        rng16 = np.random.RandomState(mul)
        for _ in range(steps):
            start = rng16.randint(0, vocab, (16, 1))
            rows = [start]
            for _ in range(24):
                rows.append((rows[-1] * mul + add) % vocab)
            ids = np.concatenate(rows, 1).astype(np.int64)
            step(paddle.to_tensor(ids[:, :-1]),
                 labels=paddle.to_tensor(ids[:, 1:]))
        tuned.eval()
        adapter = {}
        for name, p in tuned.named_parameters():
            if name.rsplit(".", 1)[-1] != "weight" \
                    or name.split(".")[-2] not in attn_leafs:
                continue
            qual = name.rsplit(".", 1)[0]
            delta = np.asarray(p.numpy(), np.float64) \
                - np.asarray(base_sd[name], np.float64)
            u, s, vt = np.linalg.svd(delta, full_matrices=False)
            k = min(lora_rank, s.size)   # thin k/v have rank <= 32
            A = np.zeros((delta.shape[0], lora_rank), np.float32)
            B = np.zeros((lora_rank, delta.shape[1]), np.float32)
            A[:, :k] = (u[:, :k] * s[:k]).astype(np.float32)
            B[:k] = vt[:k].astype(np.float32)
            adapter[qual] = (A, B)
        return adapter

    steps16 = 80 if args.tiny else 160
    tenant_a = train_adapter(7, 1, steps16)    # learns x*7+1
    tenant_b = train_adapter(3, 5, steps16)    # learns x*3+5
    scfg16 = ServingConfig(num_slots=4, block_size=16,
                           max_model_len=128, max_new_tokens=8,
                           lora_rank=lora_rank, max_adapters=4)
    # Probe with a prompt NOT on the base chain: each model continues
    # its own learned rule from the last token, so the three outputs
    # diverge (on the base chain the base model's confidence would
    # swamp the small fine-tune deltas).
    prompt16 = np.asarray([11, 14, 35], np.int64)

    def solo16(aid):
        eng = ServingEngine(model, scfg16)
        eng.load_adapter(1, tenant_a)
        eng.load_adapter(2, tenant_b)
        rid = eng.submit(prompt16.copy(), 8, adapter_id=aid)
        out = eng.run()[rid]
        eng.shutdown()
        return out

    solo = {aid: solo16(aid) for aid in (1, 2, None)}
    eng16 = ServingEngine(model, scfg16)
    eng16.load_adapter(1, tenant_a)
    eng16.load_adapter(2, tenant_b)
    rids16 = [eng16.submit(prompt16.copy(), 8, adapter_id=a)
              for a in (1, 2, None)]
    done16 = eng16.run()
    st16 = eng16.stats()
    eng16.shutdown()
    for rid, aid in zip(rids16, (1, 2, None)):
        np.testing.assert_array_equal(
            done16[rid], solo[aid],
            err_msg=f"adapter {aid}: batched != solo")
    assert st16["executables_compiled"] == 1     # ONE mixed tick
    assert st16["lora_adapters_resident"] == 2
    # the tenants learned different arithmetic: their continuations
    # of the SAME prompt must disagree with each other and the base
    outs16 = [done16[r].tolist() for r in rids16]
    assert outs16[0] != outs16[1] and outs16[0] != outs16[2] \
        and outs16[1] != outs16[2], outs16
    print(f"multi-LoRA: tenants {outs16[0]} / {outs16[1]} vs base "
          f"{outs16[2]} — batched == solo, "
          f"{st16['executables_compiled']} executable, "
          f"{st16['lora_adapters_resident']} adapters resident")

    # ---- 17. elastic autoscaling + live KV session migration --------
    # A queue burst trips the AutoscalePolicy (queue-per-slot over its
    # threshold for hysteresis_ticks) and the fleet grows; when the
    # load quiesces the fleet drains back down — and the drain
    # LIVE-MIGRATES every resident session to the survivor at its
    # exact continuation state, so the streams just continue: every
    # request, migrated or not, is token-exact vs a never-migrated
    # solo engine. Kill switch: PADDLE_TPU_AUTOSCALE=0.
    from paddle_tpu.inference.autoscale import AutoscaleConfig
    scfg17 = ServingConfig(num_slots=2, block_size=8,
                           max_model_len=96, prefill_chunk=16)
    rng17 = np.random.RandomState(17)
    burst17 = [rng17.randint(1, vocab, (n,)).astype(np.int64)
               for n in (11, 19, 9, 14)]
    ref_eng = ServingEngine(model, scfg17)
    ref17 = [ref_eng.serve([p.copy()], max_new_tokens=10)[0]
             for p in burst17]
    ref_eng.shutdown()
    elastic = EngineCluster(
        model,
        ClusterConfig(num_replicas=1, autoscale=AutoscaleConfig(
            min_replicas=1, max_replicas=2, up_queue_per_slot=0.5,
            hysteresis_ticks=2, cooldown_ticks=64)),
        scfg17)
    rids17 = [elastic.submit(p.copy(), 10) for p in burst17]
    done17 = elastic.run()              # scale-up fires mid-burst
    st17 = elastic.stats()
    assert st17["scale_ups"] == 1 and st17["replicas_live"] == 2
    # quiesce: two fresh sessions decode mid-flight while the fleet
    # shrinks back — their streams continue across the migration
    mig17 = [elastic.submit(p.copy(), 10) for p in burst17[:2]]
    for _ in range(6):                  # into decode, not yet done
        elastic.step()
    # drain the replica holding the sessions (prefix affinity parked
    # both on their turn-1 replica) — the drain live-migrates them
    busy = max(range(2), key=lambda i: elastic.engines[i].num_active)
    elastic.scale_down(busy)
    done17.update(elastic.run())
    st17 = elastic.stats()
    assert st17["sessions_migrated"] >= 1 and st17["scale_downs"] == 1
    for rid, ref in zip(rids17 + mig17, ref17 + ref17[:2]):
        assert done17[rid].tolist() == ref.tolist(), \
            "a migrated stream diverged from the never-migrated run"
    elastic.shutdown()
    print(f"elastic fleet: burst scaled 1->2 "
          f"({st17['autoscale']['decisions']['up']} policy up), "
          f"drain live-migrated {st17['sessions_migrated']} "
          f"session(s) (p99 {st17['migration_ms']['p99']:.1f} ms) — "
          f"all {len(rids17) + len(mig17)} streams token-exact")

    # ---- 18. async tick pipeline ------------------------------------
    # The default engine launches every tick before the last one's
    # tokens are fetched: the host packs tick N+1 from committed state
    # plus what tick N does to it, the decode ids it does not have yet
    # are read from tick N's output on the device, and tick N's commit
    # (emit, retire, publish) runs while N+1 executes. Admissions and
    # chunked prefill ride along; only what reads slot state from
    # outside the tick (cancel, preemption, migration, a handoff)
    # drains it. The contract is exactness: the same tokens, the same
    # schedule and one executable as the blocking loop, which an
    # explicit async_depth=0 (or PADDLE_TPU_ASYNC_TICK=0) keeps as the
    # reference.
    rng18 = np.random.RandomState(18)
    prompts18 = [rng18.randint(1, vocab, (n,)).astype(np.int64)
                 for n in (9, 13, 7)]
    outs18, st18 = {}, {}
    for name, kw in (("default", {}), ("blocking", {"async_depth": 0})):
        eng18 = ServingEngine(model, ServingConfig(
            num_slots=2, block_size=8, max_model_len=96, **kw))
        outs18[name] = eng18.serve([p.copy() for p in prompts18],
                                   max_new_tokens=10)
        st18[name] = eng18.stats()
        eng18.shutdown()
    for a, b in zip(outs18["blocking"], outs18["default"]):
        assert a.tolist() == b.tolist(), \
            "the tick dispatched ahead diverged from the blocking loop"
    on18, off18 = st18["default"], st18["blocking"]
    assert on18["async_depth"] == 1 and off18["async_depth"] == 0
    assert on18["executables_compiled"] == \
        off18["executables_compiled"] == 1
    assert on18["decode_steps"] == off18["decode_steps"]
    assert on18["pipeline_flushes"] == 0
    print(f"async tick pipeline: the default engine token-exact vs "
          f"async_depth=0 ({on18['decode_steps']} ticks both, 1 "
          f"executable, host gap p50 "
          f"{on18['host_gap_ms']['p50']:.2f} ms vs blocking "
          f"{off18['host_gap_ms']['p50']:.2f} ms, "
          f"{on18['pipeline_flushes']} flushes)")
    return n_ok / 12.0, losses


if __name__ == "__main__":
    acc, _ = main()
    assert acc > 0.8, f"served generations diverged from the chain: {acc}"
