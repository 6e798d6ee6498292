"""Serving launcher: the LLMService front-end over either backend — the real
continuous-batching engine (wall-clock) or the cost-model simulator (virtual
clock) — with a synthetic open-loop request stream. ``--instances N`` puts a
cluster RouterBackend in front of N instances (placement via ``--policy``,
cross-instance prefix sharing via ``--prefix-share``).

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --reduced --requests 16 --rate 4
  PYTHONPATH=src python -m repro.launch.serve --backend sim --requests 200
  PYTHONPATH=src python -m repro.launch.serve --backend sim --requests 400 \
      --instances 4 --policy prefix_affinity --prefix-cache --prefix-share
  PYTHONPATH=src python -m repro.launch.serve --backend sim --requests 200 \
      --roles 2p2d --handoff-mode auto
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.api import LLMService, SamplingParams


def build_netmodel(args):
    # no --net-gbps: network accounting stays off for copy AND zero_copy
    # alike (an asymmetric default would bias their comparison); share-mode
    # auto forces a model (its decision needs one), and so does explicit
    # swap-lane calibration (--pcie-gbps / --t-swap-fixed must reach the
    # backend's swap_net instead of silently using defaults)
    calibrated = args.pcie_gbps is not None or args.t_swap_fixed is not None
    if args.net_gbps is None and not calibrated \
            and args.share_mode != "auto":
        return None
    from repro.core.distkv.netmodel import NetworkModel
    kw = {}
    if args.net_gbps is not None:
        kw["gbps"] = args.net_gbps
    if args.pcie_gbps is not None:
        kw["pcie_gbps"] = args.pcie_gbps
    if args.t_swap_fixed is not None:
        kw["t_swap_fixed"] = args.t_swap_fixed
    return NetworkModel(**kw)


def build_engine(cfg, ecfg, *, seed: int = 0, device=None):
    """A :class:`PagedEngine` serving ``cfg`` with random weights drawn
    from ``seed``. The weights are made on ``device`` (default: the first
    device) and committed there, and the engine keeps its KV pools beside
    them, so N engines built for N devices are N independent replicas."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import Model
    from repro.serving.engine import PagedEngine
    device = device if device is not None else jax.devices()[0]
    init = jax.jit(Model(cfg, remat=False).init,
                   out_shardings=SingleDeviceSharding(device))
    return PagedEngine(cfg, init(jax.random.PRNGKey(seed)), ecfg)


def build_instance(args, index: int = 0):
    """Instance ``index`` of the cluster; engine instances go round-robin
    over the local devices, one replica per device."""
    telemetry = bool(args.trace or args.metrics_csv)
    if args.backend == "sim":
        from repro.serving.simulator import SimBackend
        return SimBackend(num_blocks=args.pages, block_size=args.page_size,
                          max_running=args.slots,
                          prefix_cache=args.prefix_cache,
                          chunk_policy=args.chunk_policy,
                          host_blocks=args.host_pages,
                          swap_mode=args.swap_mode,
                          victim_policy=args.victim_policy,
                          swap_overlap=args.swap_overlap,
                          speculative_swap=args.speculative_swap,
                          cache_spill_pages=args.cache_spill_pages,
                          net=build_netmodel(args), trace=telemetry)
    import jax
    from repro.serving.engine import EngineConfig
    cfg = smoke_config(args.arch) if args.reduced else get_config(args.arch)
    devices = jax.devices()
    return build_engine(cfg, EngineConfig(
        num_pages=args.pages, page_size=args.page_size,
        max_slots=args.slots, use_kernel=args.use_kernel,
        enable_prefix_cache=args.prefix_cache,
        chunk_policy=args.chunk_policy, enable_telemetry=telemetry,
        host_pages=args.host_pages, swap_mode=args.swap_mode,
        victim_policy=args.victim_policy,
        speculative_swap=args.speculative_swap,
        cache_spill_pages=args.cache_spill_pages),
        device=devices[index % len(devices)])


def parse_roles_arg(args):
    """Validate --roles early with a launcher-grade error (SystemExit, not
    a traceback), and reconcile it with --instances."""
    if args.roles is None:
        return None
    from repro.serving.disagg import parse_role_spec
    try:
        roles = parse_role_spec(args.roles)
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    if args.instances > 1 and args.instances != len(roles):
        raise SystemExit(
            f"error: --roles {args.roles!r} names {len(roles)} instances "
            f"but --instances is {args.instances} — drop --instances (the "
            f"spec sets the count) or make them agree")
    return roles


def build_backend(args):
    if args.prefix_share and not args.prefix_cache:
        raise SystemExit("--prefix-share requires --prefix-cache")
    if args.prefix_share and args.instances <= 1:
        raise SystemExit("--prefix-share requires --instances >= 2 "
                         "(there is no peer to share with)")
    if args.share_mode != "copy" and not args.prefix_share:
        raise SystemExit("--share-mode zero_copy/auto requires "
                         "--prefix-share")
    roles = parse_roles_arg(args)
    if roles is not None:
        args.instances = len(roles)
    if args.instances <= 1:
        return build_instance(args)
    from repro.serving.router import RouterBackend
    children = [build_instance(args, i) for i in range(args.instances)]
    try:
        return RouterBackend(children, policy=args.policy,
                             prefix_share=args.prefix_share,
                             share_mode=args.share_mode,
                             board_pages=args.board_pages,
                             net=build_netmodel(args),
                             roles=roles,
                             handoff_mode=args.handoff_mode)
    except ValueError as e:  # e.g. a role spec with no decode instance
        raise SystemExit(f"error: {e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("engine", "sim"), default="engine",
                    help="real PagedEngine (wall-clock) or cost-model "
                         "SimBackend (virtual clock) — same LLMService API")
    ap.add_argument("--arch", choices=ARCH_IDS, default="h2o-danube-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--best-of", type=int, default=1,
                    help="n parallel samples per prompt (COW-forked KV)")
    ap.add_argument("--use-kernel", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="force the Pallas paged-attention kernel for GQA "
                         "decode on or off (default: on where the pools live "
                         "on a TPU; interpret mode on the CPU)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix KV cache (cross-request reuse)")
    from repro.core.scheduling import CHUNK_POLICIES
    ap.add_argument("--chunk-policy", default="decode_first",
                    choices=CHUNK_POLICIES,
                    help="chunked-prefill budget policy: decode_first "
                         "(Sarathi stall-free), prefill_first (TTFT-"
                         "optimal), monolithic (whole prompt in one "
                         "iteration next to the decodes), or solo (legacy: "
                         "over-budget prompts wait for an idle engine)")
    from repro.core.scheduling.iteration import SWAP_MODES, VICTIM_POLICIES
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host (CPU) KV pages backing swap-to-host "
                         "preemption and prefix-cache spill (0 = no host "
                         "tier, preemption always recomputes)")
    ap.add_argument("--swap-mode", default="sacrifice", choices=SWAP_MODES,
                    help="what preemption does to a victim's computed KV: "
                         "sacrifice (free + re-prefill later), swap (move "
                         "to host pages over PCIe, resume without "
                         "re-prefill), or auto (per-victim cost decision)")
    ap.add_argument("--victim-policy", default="lifo",
                    choices=VICTIM_POLICIES,
                    help="which running request is preempted/swapped under "
                         "memory pressure: lifo (newest), fifo (oldest), "
                         "lru (least recently scheduled), or cost (cheapest "
                         "modeled eviction per freed page)")
    ap.add_argument("--swap-overlap", action="store_true",
                    help="sim backend: double-buffer PCIe swap DMAs against "
                         "each iteration's compute (only the surplus past "
                         "the compute time is charged)")
    ap.add_argument("--speculative-swap", action="store_true",
                    help="issue decode swap-outs one iteration early when "
                         "free pages trend under the watermark, cancelling "
                         "if pressure recedes (issue/complete halves over "
                         "the allocator's pending ledger)")
    ap.add_argument("--pcie-gbps", type=float, default=None,
                    help="swap-lane calibration: PCIe bandwidth for the "
                         "NetworkModel's device<->host swap time (default: "
                         "the model's 256 Gb/s)")
    ap.add_argument("--t-swap-fixed", type=float, default=None,
                    help="swap-lane calibration: per-batched-DMA setup time "
                         "in seconds (default: the model's 20us)")
    ap.add_argument("--cache-spill-pages", type=int, default=0,
                    help="host pages the prefix cache may use to spill "
                         "cold cached prefixes instead of evicting them "
                         "(bounded LRU; needs --host-pages and "
                         "--prefix-cache)")
    ap.add_argument("--instances", type=int, default=1,
                    help="serving instances behind the cluster router "
                         "(1 = no router)")
    ap.add_argument("--policy", default="round_robin",
                    choices=("round_robin", "least_loaded",
                             "prefix_affinity"),
                    help="router placement policy")
    ap.add_argument("--roles", default=None, metavar="SPEC",
                    help="disaggregated prefill/decode roles as "
                         "<count><p|d|m> groups, e.g. '2p2d' = 2 prefill + "
                         "2 decode instances; implies the instance count. "
                         "Prompts land on prefill instances, finished KV "
                         "is handed to decode instances")
    from repro.serving.disagg import HANDOFF_MODES
    ap.add_argument("--handoff-mode", default="auto", choices=HANDOFF_MODES,
                    help="how prefill->decode KV handoff moves the prompt "
                         "KV: migrate page payloads, zero_copy lease the "
                         "prefill host's pages in place, or auto "
                         "(per-request network-cost decision)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="publish hot radix paths through the distkv board "
                         "so instances adopt each other's cached prefixes "
                         "(needs --prefix-cache)")
    ap.add_argument("--board-pages", type=int, default=None,
                    help="size cap (pages) for the cross-instance "
                         "publication board; LRU pages are evicted past it "
                         "(default: unbounded)")
    from repro.serving.router import SHARE_MODES
    ap.add_argument("--share-mode", default="copy", choices=SHARE_MODES,
                    help="how a published prefix reaches a peer instance: "
                         "copy its page payloads once, zero_copy serve it "
                         "in place over borrowed rBlocks (DistAttention "
                         "partial merge), or auto (per-request network-"
                         "cost decision)")
    ap.add_argument("--net-gbps", type=float, default=None,
                    help="interconnect bandwidth for the network cost "
                         "model (sim backend charges payload copies and "
                         "lease RPCs; default: no network accounting, "
                         "except share-mode auto which needs the model)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable telemetry and export a Chrome/Perfetto "
                         "trace-event JSON (open in ui.perfetto.dev or "
                         "chrome://tracing) after the run")
    ap.add_argument("--metrics-csv", metavar="PATH", default=None,
                    help="enable telemetry and dump per-iteration metric "
                         "timelines (one row per instance-iteration) as "
                         "CSV after the run")
    args = ap.parse_args()

    enable_compile_cache()
    backend = build_backend(args)
    svc = LLMService(backend)
    instance = backend.children[0] if hasattr(backend, "children") \
        else backend
    vocab = 32_000 if args.backend == "sim" else instance.cfg.vocab_size

    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        svc.submit(rng.integers(0, vocab, plen).tolist(),
                   SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  n=args.best_of,
                                  max_new_tokens=int(rng.integers(
                                      2, args.max_new)),
                                  seed=int(i)),
                   arrival_time=float(arrivals[i]))

    t0 = time.monotonic()
    while svc.pending:
        now = time.monotonic() - t0 if args.backend == "engine" else None
        for ch in svc.poll(now):
            if ch.finished:
                t = ch.time if ch.time is not None else now
                print(f"[{t:7.2f}s] req {ch.request_id} done: "
                      f"{ch.n_generated} tokens ({ch.finish_reason})")
        if args.backend == "engine" and not backend.has_work and svc.pending:
            time.sleep(0.005)  # wait for the next wall-clock arrival

    stats = svc.stats()
    dt = time.monotonic() - t0 if args.backend == "engine" else stats.makespan
    print(f"served {stats.n_finished}/{stats.n_requests} requests, "
          f"{stats.total_tokens} tokens in {dt:.1f}s "
          f"({stats.total_tokens / max(dt, 1e-9):.1f} tok/s, "
          f"{backend.iterations} iterations); "
          f"mean ttft {stats.mean_ttft * 1e3:.1f}ms, "
          f"mean norm-lat {stats.mean_normalized_latency:.3f}s/tok")
    if stats.p99_tbt != float("inf"):
        print(f"p99 worst inter-token gap {stats.p99_tbt * 1e3:.1f}ms, "
              f"prefill stall {stats.prefill_stall_ms:.1f}ms "
              f"(chunk policy: {args.chunk_policy})")
    if stats.prefix_hit_rate is not None:
        print(f"prefix-cache hit-rate {stats.prefix_hit_rate:.1%}")
    kids = getattr(backend, "children", [backend])
    n_so = sum(getattr(c, "swapped_out", 0) for c in kids)
    n_si = sum(getattr(c, "swapped_in", 0) for c in kids)
    if n_so or n_si:
        print(f"host swap: {n_so} swap-outs, {n_si} swap-ins "
              f"(mode: {args.swap_mode}, victims: {args.victim_policy}, "
              f"{args.host_pages} host pages)")
    if getattr(backend, "pages_borrowed", 0):
        print(f"zero-copy: {backend.leases_granted} leases, "
              f"{backend.pages_borrowed} pages served remotely "
              f"(share mode: {args.share_mode})")
    ho = getattr(backend, "handoff", None)
    if ho is not None:
        print(f"disagg: {ho.handoffs_migrated} migrated + "
              f"{ho.handoffs_leased} leased KV handoffs "
              f"({ho.pages_copied} pages copied, {ho.pages_leased} leased, "
              f"{ho.deferrals} deferrals, {ho.fallbacks} fallbacks; "
              f"mode: {args.handoff_mode})")
    if stats.per_instance:
        for i, row in sorted(stats.per_instance.items()):
            extra = ""
            if "prefix_hit_rate" in row:
                extra = (f", hit {row['prefix_hit_rate']:.1%}, "
                         f"{row['adopted_pages']} adopted pages")
            print(f"  instance {i}: {row['requests']} reqs, "
                  f"{row['iterations']} iters{extra}")
    if args.trace:
        n = svc.export_trace(args.trace)
        print(f"wrote {n} trace events to {args.trace} "
              f"(open in https://ui.perfetto.dev)")
    if args.metrics_csv:
        n = svc.export_metrics_csv(args.metrics_csv)
        print(f"wrote {n} metric rows to {args.metrics_csv}")


if __name__ == "__main__":
    main()
