"""Continuous-batching LM serving demo: resident vs host-paged KV cache.

Serves a synthetic request stream through the decode engine and reports the
per-device HBM cache footprint of the chosen plan — the paged plan keeps a
hot window in HBM and pages the cold cache to host memory, which is the
point: long-context decode stops being bounded by HBM.

    PYTHONPATH=src python examples/serve_lm.py --plan paged --seq-len 128 \
        --requests 4 --max-new 8 --page-size 16 --hot-pages 2

``--plan resident`` runs the fully HBM-resident baseline; ``--plan paged``
forces the page-table cache; CI runs both as the serve-paged-parity gate
(the sampled tokens must match across plans for identical request streams).
``--admission`` picks how prompts enter the cache: ``chunked`` (default for
attentive configs) interleaves prefill chunks with decode ticks, ``whole``
runs each prompt's prefill to completion, ``replay`` teacher-forces the
prompt one token per tick (default for attention-free configs).
"""
import argparse

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core.plan import MemoryPlan
from repro.launch.mesh import make_local_mesh
from repro.models import kvcache as KV
from repro.models import model as M
from repro.serve import DecodeEngine, Request, choose_paging


def build_requests(n: int, vocab: int, max_new: int) -> list[Request]:
    key = jax.random.PRNGKey(7)
    prompts = jax.random.randint(key, (n, 4), 1, vocab)
    return [Request(i, [int(t) for t in prompts[i]], max_new) for i in range(n)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-405b")
    ap.add_argument("--plan", choices=["resident", "paged"], default="paged")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--hot-pages", type=int, default=2)
    ap.add_argument("--admission", default="auto",
                    choices=["auto", "replay", "chunked", "whole"],
                    help="prompt ingestion: chunked prefill interleaved "
                         "with decode (default for attentive configs), "
                         "whole-prompt prefill, or teacher-forced replay")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="override the cost-model prefill chunk size")
    ap.add_argument("--compiled-memory", action="store_true",
                    help="also AOT-compile the step to report XLA's per-"
                         "device argument bytes (a second full compile)")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="also append every log line as a structured JSONL "
                         "record (obs.StructuredLogger)")
    args = ap.parse_args()
    log = obs.StructuredLogger("serve_lm", jsonl_path=args.log_jsonl)

    cfg = reduced(get_config(args.arch))
    mesh = make_local_mesh()
    n_dev = mesh.devices.size
    shape = ShapeConfig("serve", args.seq_len, args.batch_slots, "decode")
    s_kv = KV.cache_len(cfg, args.seq_len)

    paging = None
    nc, nb = 3, 2  # embed + blocks + head (labels the plan; weights persist)
    if args.plan == "paged":
        paging = choose_paging(s_kv, args.page_size, args.hot_pages)
        plan = MemoryPlan(nc, nb, n_persist=nc, n_host=paging.n_cold)
        log.info("plan",
                 f"[serve_lm] paged: {paging} "
                 f"(hot {paging.hot_window}/{s_kv} tokens, "
                 f"{paging.n_cold} cold pages -> host)",
                 plan="paged", hot_window=paging.hot_window,
                 n_cold=paging.n_cold, s_kv=s_kv)
    else:
        plan = MemoryPlan(nc, nb, n_persist=nc)
        log.info("plan", f"[serve_lm] resident: full {s_kv}-token cache in HBM",
                 plan="resident", s_kv=s_kv)

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = DecodeEngine(
        cfg, plan, mesh, shape, params, paging=paging,
        admission=None if args.admission == "auto" else args.admission,
        prefill_chunk=args.prefill_chunk or None)

    dev_args = None
    if args.compiled_memory:
        # measured per-device memory of the compiled step (args hold the
        # cache); a second compile, so opt-in — CI runs without it
        mem = engine.art.lower(donate=False).compile().memory_analysis()
        dev_args = mem.argument_size_in_bytes

    engine.submit(build_requests(args.requests, cfg.vocab_size, args.max_new))
    report = engine.run()
    tok_s = report.generated_tokens / max(report.wall_s, 1e-9)
    log.info("served",
             f"[serve_lm] served {len(report.finished)} requests, "
             f"{report.generated_tokens} tokens in {report.steps} steps "
             f"({report.prefill_ticks} prefill / {report.decode_ticks} decode, "
             f"admission={report.admission}"
             + (f", chunk={report.prefill_chunk}" if report.prefill_chunk else "")
             + f"; {tok_s:.1f} tok/s, evictions={report.evictions}"
             + ("" if report.drained else f", STOPPED with pending={report.pending}")
             + ")",
             **report.to_dict())
    log.info("latency",
             f"[serve_lm] latency p50/p99 {report.p50_latency_s:.4f}/"
             f"{report.p99_latency_s:.4f}s, TTFT p50/p99 {report.p50_ttft_s:.4f}/"
             f"{report.p99_ttft_s:.4f}s, p99 ITL {report.p99_itl_s:.4f}s")
    for rid in sorted(report.finished):
        print(f"  req {rid}: {report.finished[rid]}")
    hbm_dev = report.hbm_cache_bytes / n_dev
    res_dev = report.resident_cache_bytes / n_dev
    log.info("memory",
             f"[serve_lm] per-device HBM cache: {hbm_dev / 1e6:.3f} MB "
             f"(resident layout: {res_dev / 1e6:.3f} MB) "
             f"-> reduction x{report.hbm_reduction:.2f}; "
             f"host pages: {report.host_cache_bytes / n_dev / 1e6:.3f} MB/device",
             hbm_dev_bytes=int(hbm_dev), resident_dev_bytes=int(res_dev),
             hbm_reduction=round(report.hbm_reduction, 2))
    if dev_args is not None:
        log.info("compiled_memory",
                 f"[serve_lm] compiled per-device argument bytes: "
                 f"{dev_args / 1e6:.3f} MB", argument_bytes=int(dev_args))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
