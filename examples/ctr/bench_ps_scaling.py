"""N-worker concurrent PS sd_pushpull scaling bench (VERDICT r2 item 7).

Reference counterpart: ps-lite's multi-worker keyed RPC throughput
(tests/pstests/test_bandwidth.py pattern).  One TCP PSServer on
localhost, N worker PROCESSES each hammering sd_pushpull on a shared
embedding table (zipf-skewed ids, the CTR regime); reports aggregate
embedding rows/s per worker count and writes BENCH_PS_SCALING.json into
the directory it is run from.

Run: python examples/ctr/bench_ps_scaling.py [--rows 1000000]
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import socket
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


_OUT = "BENCH_PS_SCALING.json"     # written where the bench is run


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _make_client(ports):
    from hetu_tpu.ps.client import PSClient, _TCPTransport
    if len(ports) > 1:
        from hetu_tpu.ps.sharded import ShardedPSClient
        return ShardedPSClient(
            addrs=[f"127.0.0.1:{p}" for p in ports])
    return PSClient(transport=_TCPTransport("127.0.0.1", ports[0]))


def _timed_pushpull(make, close, key, batch, dim, iters, nrows, seed, q,
                    barrier):
    """Shared measurement body: one warmup round-trip, barrier-aligned
    timed window, rows/s onto the queue.  Both tiers (python PSServer
    client, native van client) run EXACTLY this loop so their numbers
    stay comparable."""
    rng = np.random.RandomState(seed)
    c = make()
    ids = ((rng.zipf(1.05, size=(iters, batch)) - 1) % nrows)
    rows = rng.randn(batch, dim).astype(np.float32)
    # warmup (connection + first apply), then line up: the timed windows
    # must overlap or process spawn/import time pollutes the aggregate
    c.sd_pushpull(key, ids[0], rows)
    barrier.wait()
    t0 = time.perf_counter()
    for i in range(iters):
        c.sd_pushpull(key, ids[i], rows)
    dt = time.perf_counter() - t0
    q.put(batch * iters / dt)
    close(c)


def _worker(ports, key, batch, dim, iters, nrows, seed, q, barrier):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    _timed_pushpull(lambda: _make_client(ports), lambda c: c.finalize(),
                    key, batch, dim, iters, nrows, seed, q, barrier)


def _van_worker(port, batch, dim, iters, nrows, seed, q, barrier):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from hetu_tpu.ps.van import VanClient
    _timed_pushpull(lambda: VanClient("127.0.0.1", port, dim=dim),
                    lambda c: c.close(), 0, batch, dim, iters, nrows,
                    seed, q, barrier)


def _van_serve(port, rows, dim, ready):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from hetu_tpu.ps.van import NativeVan
    van = NativeVan()
    van.listen(port)
    van.register_sgd_table(0, np.zeros((rows, dim), np.float32),
                           lr=0.01)
    ready.set()
    while True:
        time.sleep(3600)


def _fan_out(ctx, target, args_for, n):
    """Spawn n measured workers, collect barrier-aligned rates."""
    q = ctx.Queue()
    barrier = ctx.Barrier(n)
    procs = [ctx.Process(target=target, args=args_for(r, q, barrier))
             for r in range(n)]
    for p in procs:
        p.start()
    rates = [q.get(timeout=300) for _ in procs]
    for p in procs:
        p.join()
    return rates


def quant_ab(iters=20, dense_shape=(512, 1024), sparse_batch=4096,
             dim=16, rows=100_000):
    """Int8 PS wire A/B (ISSUE 9): the SAME dense push/pull and sparse
    sd_pushpull traffic against one TCP PSServer, exact f32 vs
    ``HETU_PS_QUANT=int8``, measured by the PR 5 per-shard
    ``ps.rpc.bytes_sent/recv`` counters — the artifact records the wire
    bytes, the reduction ratio (acceptance floor 3.5x, ASSERTED), the
    ``ps.rpc.bytes_saved`` counter, and wall time per round trip.
    Returns the ``quant_ab`` dict merged into BENCH_PS_SCALING.json."""
    from hetu_tpu import envvars, quant, telemetry
    from hetu_tpu.ps.client import PSClient, _TCPTransport

    port = _free_port()
    ctx = mp.get_context("spawn")
    srv = ctx.Process(target=_serve, args=(port,), daemon=True)
    srv.start()
    _wait(port)
    rng = np.random.RandomState(7)
    dense_grad = rng.randn(*dense_shape).astype(np.float32)
    ids = ((rng.zipf(1.05, size=(iters, sparse_batch)) - 1) % rows)
    sparse_rows = rng.randn(sparse_batch, dim).astype(np.float32)

    def measure(mode):
        old = envvars.get_raw("HETU_PS_QUANT")
        if mode:
            os.environ["HETU_PS_QUANT"] = mode
        else:
            os.environ.pop("HETU_PS_QUANT", None)
        telemetry.reset()
        c = PSClient(transport=_TCPTransport("127.0.0.1", port))
        try:
            key = f"qab_{mode or 'off'}"
            c.param_set(key, np.zeros(dense_shape, np.float32),
                        opt="sgd", opt_args={"learning_rate": 0.01})
            c.param_set(key + "_emb", np.zeros((rows, dim), np.float32),
                        opt="sgd", opt_args={"learning_rate": 0.01})
            c.push(key, dense_grad)          # warm the connection
            telemetry.reset()
            t0 = time.perf_counter()
            for i in range(iters):
                c.push(key, dense_grad)
                c.pull(key)
                c.sd_pushpull(key + "_emb", ids[i], sparse_rows)
            dt = time.perf_counter() - t0
            snap = telemetry.snapshot()["counters"]
            out = {
                "quant": mode or "off",
                "iters": iters,
                "wall_s": round(dt, 3),
                "ms_per_round": round(dt / iters * 1e3, 3),
                "bytes_sent": int(snap.get("ps.rpc.bytes_sent", 0)),
                "bytes_recv": int(snap.get("ps.rpc.bytes_recv", 0)),
                "bytes_saved": int(snap.get("ps.rpc.bytes_saved", 0)),
            }
            out["bytes_total"] = out["bytes_sent"] + out["bytes_recv"]
            return out
        finally:
            c.finalize()
            if old is None:
                os.environ.pop("HETU_PS_QUANT", None)
            else:
                os.environ["HETU_PS_QUANT"] = old

    try:
        exact = measure(None)
        int8 = measure("int8")
    finally:
        srv.terminate()
    ratio = round(exact["bytes_total"] / max(int8["bytes_total"], 1), 2)
    section = {
        "config": {"dense_shape": list(dense_shape),
                   "sparse_batch": sparse_batch, "dim": dim,
                   "rows": rows, "iters": iters,
                   "chunk": quant.DEFAULT_CHUNK,
                   "traffic": "dense push + dense pull + sparse "
                              "sd_pushpull per round",
                   "counters": "ps.rpc.bytes_sent/recv (PR 5), "
                               "ps.rpc.bytes_saved (this PR)"},
        "exact": exact,
        "int8": int8,
        "wire_reduction": ratio,
        "note": "symmetric per-chunk int8 + f32 scales on the typed "
                "wire (ps/wire.py tag Q); dequantized server-side "
                "before the optimizer step, symmetrically on pull; "
                "acceptance floor 3.5x asserted",
    }
    assert ratio >= 3.5, (
        f"int8 PS wire reduction {ratio}x below the 3.5x acceptance "
        f"floor: {exact} vs {int8}")
    path = _OUT
    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, ValueError):
        art = {"bench": "ps_sd_pushpull_scaling"}
    art["quant_ab"] = section
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    print(json.dumps({"quant_ab_wire_reduction": ratio,
                      "ms_per_round_exact": exact["ms_per_round"],
                      "ms_per_round_int8": int8["ms_per_round"]}))
    return section


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--workers", default="1,2,4,8")
    ap.add_argument("--servers", default="1,4",
                    help="server-group sizes to sweep (row-sharded)")
    ap.add_argument("--quant-only", action="store_true",
                    help="run ONLY the int8-wire quant A/B and merge "
                         "its quant_ab section into "
                         "BENCH_PS_SCALING.json")
    args = ap.parse_args()

    if args.quant_only:
        quant_ab()
        return

    ctx = mp.get_context("spawn")
    results = {}
    server_counts = [int(x) for x in args.servers.split(",")]
    worker_counts = [int(x) for x in args.workers.split(",")]
    for ns in server_counts:
        ports = [_free_port() for _ in range(ns)]
        srvs = [ctx.Process(target=_serve, args=(p,), daemon=True)
                for p in ports]
        for s in srvs:
            s.start()
        for p in ports:
            _wait(p)
        admin = _make_client(ports)
        # param_set (not parameter_init): the sharded client row-shards
        # explicit 2-D values across the group — the executor bridge path
        admin.param_set("emb", np.zeros((args.rows, args.dim), np.float32),
                        opt="sgd", opt_args={"learning_rate": 0.01})
        for n in worker_counts:
            # barrier-aligned windows: the sum of concurrent per-worker
            # rates is the aggregate service rate
            rates = _fan_out(
                ctx, _worker,
                lambda r, q, b: (ports, "emb", args.batch, args.dim,
                                 args.iters, args.rows, 100 + r, q, b),
                n)
            agg = sum(rates)
            results[f"{n}w_{ns}s"] = {
                "aggregate_rows_per_sec": round(agg, 1),
                "per_worker_rows_per_sec": [round(r, 1) for r in rates],
            }
            print(f"workers={n} servers={ns}: "
                  f"{agg/1e6:.3f}M rows/s aggregate")
        admin.finalize()
        for s in srvs:
            s.terminate()

    # ---- native C++ van tier (ps-lite zmq_van role) ----
    # 4x window: the van is ~7x faster, same wall time per cell (recorded)
    van_iters = args.iters * 4
    port = _free_port()
    ready = ctx.Event()
    srv = ctx.Process(target=_van_serve,
                      args=(port, args.rows, args.dim, ready),
                      daemon=True)
    srv.start()
    if not ready.wait(60):
        raise TimeoutError(
            "van server did not come up (register/listen stalled)")
    _wait(port)
    for n in worker_counts:
        rates = _fan_out(
            ctx, _van_worker,
            lambda r, q, b: (port, args.batch, args.dim, van_iters,
                             args.rows, 100 + r, q, b),
            n)
        agg = sum(rates)
        results[f"van_{n}w"] = {
            "aggregate_rows_per_sec": round(agg, 1),
            "per_worker_rows_per_sec": [round(r, 1) for r in rates],
        }
        print(f"van workers={n}: {agg/1e6:.3f}M rows/s aggregate")
    srv.terminate()

    # in-process single stream: the van's service rate with no
    # second python process competing for the core
    from hetu_tpu.ps.van import NativeVan, VanClient
    van = NativeVan()
    vport = van.listen()
    van.register_sgd_table(0, np.zeros((args.rows, args.dim),
                                       np.float32), lr=0.01)
    cli = VanClient("127.0.0.1", vport, dim=args.dim)
    rng = np.random.RandomState(0)
    vids = ((rng.zipf(1.05, args.batch) - 1) % args.rows)
    vrows = rng.randn(args.batch, args.dim).astype(np.float32)
    for _ in range(3):
        cli.sd_pushpull(0, vids, vrows)
    t0 = time.perf_counter()
    vit = van_iters
    for _ in range(vit):
        cli.sd_pushpull(0, vids, vrows)
    vr = args.batch * vit / (time.perf_counter() - t0)
    results["van_inprocess_single_stream"] = {
        "aggregate_rows_per_sec": round(vr, 1)}
    print(f"van in-process single stream: {vr/1e6:.3f}M rows/s")
    cli.close()
    van.stop()

    base = results[f"{worker_counts[0]}w_{server_counts[0]}s"][
        "aggregate_rows_per_sec"]
    ncpu = os.cpu_count()
    out = {
        "bench": "ps_sd_pushpull_scaling",
        "config": {"rows": args.rows, "dim": args.dim,
                   "batch": args.batch, "iters": args.iters,
                   "van_iters": args.iters * 4,
                   "transport": "tcp-localhost (python PSServer) + native C++ van (van_Kw rows)", "server_opt": "sgd",
                   "id_skew": "zipf(1.05)", "host_cpu_cores": ncpu,
                   "note": "Kw_Ns = K concurrent worker processes vs an "
                           "N-server row-sharded group. On a "
                           f"{ncpu}-core host every process shares the "
                           "same core(s); the sweep demonstrates "
                           "stability of the aggregate under 8x "
                           "concurrency (no collapse), not parallel "
                           "speedup — that needs cores. van_Kw rows: the "
                           "C++ serving loop (ps/van.py) over TCP; "
                           "van_inprocess_single_stream is its service "
                           "rate with no competing client process — the "
                           "ONE measured van headline figure (earlier "
                           "prose claimed ~16M from a different window; "
                           "the results block is authoritative). "
                           "Multi-process van rows are bounded by the "
                           "PYTHON CLIENTS sharing the same core"},
        "results": results,
        "scaling_vs_base": {k: round(r["aggregate_rows_per_sec"] / base, 2)
                            for k, r in results.items()},
    }
    with open(_OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["scaling_vs_base"]))


def _serve(port):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    os.environ["HETU_PS_PORT"] = str(port)
    from hetu_tpu.ps.server import PSServer
    PSServer.serve_from_env()


def _wait(port, timeout=20.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            s = socket.create_connection(("127.0.0.1", port), timeout=1.0)
            s.close()
            return
        except OSError:
            time.sleep(0.1)
    raise TimeoutError("PS server did not come up")


if __name__ == "__main__":
    main()
