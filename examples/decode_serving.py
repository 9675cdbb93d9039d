"""Autoregressive decode serving demo (docs/serving.md): prefill/decode
split with a paged, int8-quantizable KV cache and token-level continuous
batching — `serving.DecodeEngine`.

Shows the decode surface end to end:
 1. warm up an int8-KV engine (every prompt-bucket x batch-bucket program
    compiles once), then flood it with skewed prompt/generation lengths
    and prove ZERO fresh compiles,
 2. token-level continuous batching: sequences admit and retire
    mid-flight, so peak concurrency exceeds `max_decode_batch` requests
    served back to back,
 3. the paged-KV memory story: blocks held scale with actual generated
    length, and int8 pages fit several times more concurrent sequences
    into the same byte budget than an f32 contiguous cache,
 4. fleet membership: `deploy_decode` + per-token SLOs, then a replica
    killed mid-service — failover restarts the sequence from token 0 on
    a healthy replica and counts it.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402


def main():
    from deeplearning4j_tpu.serving import (DecodeEngine, LatencySLO,
                                            ModelFleet, TinyDecodeModel)

    model = TinyDecodeModel(vocab=96, d_model=64, n_heads=4, seed=0)
    rng = np.random.RandomState(0)

    # 1. int8-KV engine: warm every bucket, then a skewed flood recompiles
    #    nothing — prompt lengths bucket to pow2, batch rows bucket to
    #    pow2, block tables have a fixed max_pages width
    eng = DecodeEngine(model, kv_dtype="int8", num_blocks=96,
                       max_seq_len=64, max_decode_batch=4,
                       model_label="demo")
    programs = eng.warmup()
    baseline = eng.fresh_compiles()
    print(f"warmup compiled {programs} programs "
          f"({eng.fresh_compiles()} jit entries)")

    lens = [3, 5, 9, 14, 20, 33] * 3
    futs = [eng.submit(rng.randint(1, 96, size=n),
                       max_new_tokens=int(rng.randint(3, 12)),
                       deadline_ms=30_000.0)
            for n in lens]
    outs = [f.result(timeout=60) for f in futs]
    assert eng.fresh_compiles() == baseline
    toks = sum(len(o) for o in outs)
    print(f"flood: {len(outs)} sequences / {toks} tokens, prompt lengths "
          f"{sorted(set(lens))}, fresh compiles after warmup: "
          f"{eng.fresh_compiles() - baseline}")

    # 2. continuous batching: 18 sequences through a 4-row decode batch —
    #    a retiring sequence frees its row (and KV blocks) the same step,
    #    so the next waiting prompt admits mid-flight
    st = eng.stats()
    print(f"token-level batching: max_decode_batch=4 served "
          f"{len(outs)} sequences back to back; KV high water "
          f"{st['kv']['high_water']}/{st['kv']['blocks_total']} blocks, "
          f"now {st['kv']['blocks_in_use']} in use (all released)")

    # 3. memory A/B: paged int8 vs contiguous f32 worst-case reservation
    contig_f32 = 64 * model.n_heads * (model.d_model // model.n_heads) * 2 * 4
    one_seq_blocks = -(-15 // eng.page_size)   # 9 prompt + 6 generated
    paged_bytes = one_seq_blocks * eng.cache.bytes_per_block
    print(f"memory per sequence: contiguous f32 reserves {contig_f32} B "
          f"(max_seq_len worst case); paged int8 holds {paged_bytes} B "
          f"({one_seq_blocks} blocks for a 15-token sequence) — "
          f"{contig_f32 / paged_bytes:.1f}x denser")
    eng.shutdown()

    # 4. fleet membership + failover: decode members route through the
    #    same SLO admission path; a killed replica's sequences restart
    #    from token 0 on the live one (KV dies with the replica) and the
    #    restart is counted — an explicit cost, never a silent one
    from deeplearning4j_tpu.monitor.instrument import decode_instruments
    fleet = ModelFleet(max_resident=2)

    def factory(slice_):
        e = DecodeEngine(model, kv_dtype="int8", num_blocks=64,
                         max_seq_len=64, max_decode_batch=4,
                         model_label="gen")
        e.warmup()
        return e

    member = fleet.deploy_decode("gen", factory,
                                 slo=LatencySLO(target_p99_ms=1000.0),
                                 replicas=2)
    out = fleet.generate("gen", np.arange(1, 6),
                         max_new_tokens=5).result(timeout=60)
    print(f"fleet decode member '{member.name}' (kind={member.kind}, "
          f"{len(member.group.replicas)} replicas) generated "
          f"{len(out)} tokens; per-token SLO samples: "
          f"{member.latency.count}")

    before = decode_instruments().restarts("gen").value
    member.group.replicas[0].server.engine.kill()
    outs = [fleet.generate("gen", np.arange(1, 6),
                           max_new_tokens=3).result(timeout=60)
            for _ in range(6)]
    restarts = decode_instruments().restarts("gen").value - before
    print(f"replica 0 killed mid-service: {len(outs)}/6 sequences still "
          f"completed, {int(restarts)} restarted from token 0 on the "
          f"live replica (decode_sequence_restarts_total)")

    rec = fleet.controller.reconcile()
    heals = [a for a in rec["actions"] if a.get("kind") == "decode"]
    print(f"controller heal: {heals[0]['action']} cause="
          f"{heals[0]['cause']} — member respawns={member.respawns}, "
          f"readyz={fleet.readyz()['ready']}")
    fleet.shutdown()
    print("engine drained and fleet shut down")


if __name__ == "__main__":
    main()
