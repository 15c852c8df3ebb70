"""NCF training (reference examples/rec/run_hetu.py + hetu_ncf.py).

MovieLens implicit-feedback NeuMF; synthetic interactions stand in when
the dataset is absent.
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
from hetu_tpu.models import neural_mf

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("ncf")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-users", type=int, default=6040)
    parser.add_argument("--num-items", type=int, default=3706)
    parser.add_argument("--batch-size", type=int, default=1024)
    parser.add_argument("--num-steps", type=int, default=100)
    parser.add_argument("--learning-rate", type=float, default=0.01)
    parser.add_argument("--negative-ratio", type=int, default=4)
    parser.add_argument("--data-path", default=None,
                        help="dir with reference-format movielens "
                             "ratings.csv / ratings.dat; synthetic "
                             "interactions when unset")
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    rng = np.random.RandomState(0)
    bs = args.batch_size
    data = None
    if args.data_path:
        # reference-format movielens (ratings.csv / ratings.dat —
        # hetu_tpu.data.load_movielens)
        from hetu_tpu.data import load_movielens
        us, its, labs, nu, ni = load_movielens(
            args.data_path, num_negatives=args.negative_ratio)
        args.num_users, args.num_items = nu, ni
        data = (us, its, labs.reshape(-1, 1))
        logger.info("loaded movielens from %s: %d triples, %d users, "
                    "%d items", args.data_path, len(us), nu, ni)

    user = ht.placeholder_op("user_input")
    item = ht.placeholder_op("item_input")
    y_ = ht.placeholder_op("y_")
    loss, pred, train_op = neural_mf(
        user, item, y_, num_users=args.num_users, num_items=args.num_items,
        lr=args.learning_rate)
    executor = ht.Executor({"train": [loss, pred, train_op]})
    t0 = time.time()
    for step in range(args.num_steps):
        if data is not None:
            sel = rng.randint(0, len(data[0]), bs)
            users, items, labels = (data[0][sel], data[1][sel],
                                    data[2][sel])
        else:
            users = rng.randint(0, args.num_users, (bs,)).astype(np.int32)
            items = rng.randint(0, args.num_items, (bs,)).astype(np.int32)
            labels = (rng.rand(bs, 1) < 1.0 / (1 + args.negative_ratio))\
                .astype(np.float32)
        out = executor.run("train", feed_dict={
            user: users, item: items, y_: labels})
        if step % 20 == 0 or step == args.num_steps - 1:
            dt = time.time() - t0
            logger.info("step %d loss=%.4f (%.0f samples/s)", step,
                        float(np.asarray(out[0]).reshape(-1)[0]),
                        (step + 1) * bs / dt)


if __name__ == "__main__":
    main()
