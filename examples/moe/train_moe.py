"""MoE training (reference examples/moe/test_moe_*.py unified).

Gate selected by --gate {top,hash,ktop1,sam,balance}; expert parallelism
over the 'ep' mesh axis via --all2all-size N (all_to_all over ICI instead
of the reference's NCCL alltoall, SURVEY.md §2.5 Expert parallel row).
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), '..', '..'))

import argparse
import logging
import time

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import moe_mlp

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("moe")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=16)
    parser.add_argument("--num-tokens", type=int, default=1024)
    parser.add_argument("--model-dim", type=int, default=2048)
    parser.add_argument("--hidden-size", type=int, default=2048)
    parser.add_argument("--num-local-experts", type=int, default=2)
    parser.add_argument("--all2all-size", type=int, default=1)
    parser.add_argument("--gate", default="top",
                        choices=["top", "hash", "ktop1", "sam", "balance"])
    parser.add_argument("--top-k", type=int, default=2)
    parser.add_argument("--hierarchical", action="store_true",
                        help="two-stage A2A over (dcn, ici) axes")
    parser.add_argument("--num-steps", type=int, default=20)
    parser.add_argument("--learning-rate", type=float, default=0.01)
    parser.add_argument("--bf16", action="store_true",
                        help="bf16 compute, fp32 masters")
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_classes = args.model_dim
    # feed through the dataloader prefetch ring with sparse int labels:
    # a one-hot (B*T, C=model_dim) fp32 target is ~100 MB/step of
    # host->device traffic; int32 ids are ~64 KB
    rng = np.random.RandomState(0)
    n_batches = 4
    xs = rng.normal(size=(n_batches * args.batch_size, args.num_tokens,
                          args.model_dim)).astype(np.float32)
    if args.bf16:
        # halve the H2D bytes for the token feed; compute is bf16 anyway
        import ml_dtypes
        xs = xs.astype(ml_dtypes.bfloat16)
    targets = rng.randint(
        0, n_classes, size=(n_batches * args.batch_size, args.num_tokens)
    ).astype(np.int32)
    x = ht.dataloader_op([ht.Dataloader(xs, args.batch_size, "train")])
    yb = ht.dataloader_op([ht.Dataloader(targets, args.batch_size,
                                         "train")])
    y_ = ht.array_reshape_op(yb, [args.batch_size * args.num_tokens])

    # --all2all-size N over N+ devices: experts shard over the 'ep' mesh
    # axis and the token exchange is a REAL all_to_all (reference NCCL
    # alltoall, gpu_ops/AllToAll.py); --hierarchical uses a (dcn, ici)
    # mesh so the exchange stages intra- then inter-group
    mesh, strategy = None, None
    ep = args.all2all_size
    if ep > 1:
        if args.gate == "balance":
            raise SystemExit(
                "--gate balance uses the per-local-expert balance-"
                "assignment formulation, which has no expert-parallel "
                "lowering; drop --all2all-size")
        import jax
        from hetu_tpu.parallel.mesh import make_mesh
        n_dev = jax.device_count()
        if n_dev % ep:
            raise SystemExit(f"--all2all-size {ep} needs a device count "
                             f"divisible by it (have {n_dev})")
        if args.hierarchical:
            if ep % 2 or ep < 4:
                raise SystemExit("--hierarchical needs an even "
                                 "--all2all-size >= 4 (dcn x ici mesh)")
            if n_dev != ep:
                raise SystemExit(
                    f"--hierarchical builds a dcn x ici mesh of exactly "
                    f"--all2all-size devices; have {n_dev}, want {ep} "
                    f"(the non-hierarchical path adds a dp axis instead)")
            from jax.sharding import PartitionSpec as P
            mesh = make_mesh({"dcn": 2, "ici": ep // 2})
            # experts shard over the combined (dcn, ici) superaxis
            strategy = ht.dist.ShardingPlan({
                "expert_expert_stack_w1": P(("dcn", "ici"), None, None),
                "expert_expert_stack_w2": P(("dcn", "ici"), None, None)})
        else:
            dp = n_dev // ep
            strategy = ht.dist.ExpertParallel(ep=ep, dp=dp)
    loss, y = moe_mlp(
        x, y_, batch_size=args.batch_size, num_tokens=args.num_tokens,
        model_dim=args.model_dim, hidden_size=args.hidden_size,
        num_local_experts=args.num_local_experts,
        all2all_size=args.all2all_size, gate_type=args.gate,
        top_k=args.top_k, hierarchical=args.hierarchical,
        sparse_labels=True, expert_parallel=ep > 1)
    train_op = ht.optim.SGDOptimizer(
        learning_rate=args.learning_rate).minimize(loss)
    executor = ht.Executor({"train": [loss, train_op]}, mesh=mesh,
                           dist_strategy=strategy,
                           mixed_precision="bf16" if args.bf16 else None)

    out = executor.run("train")                       # compile + warmup
    logger.info("step 0 loss=%.4f (compile)",
                float(np.asarray(out[0]).reshape(-1)[0]))
    t0 = time.time()
    for step in range(1, args.num_steps):
        out = executor.run("train")
        if step % 5 == 0 or step == args.num_steps - 1:
            dt = time.time() - t0
            tok_s = step * args.batch_size * args.num_tokens / dt
            logger.info("step %d loss=%.4f (%.0f tokens/s)", step,
                        float(np.asarray(out[0]).reshape(-1)[0]), tok_s)


if __name__ == "__main__":
    main()
