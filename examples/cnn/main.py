"""CNN-family training (reference examples/cnn/main.py).

Usage:
    python examples/cnn/main.py --model resnet18 --dataset CIFAR10 \
        --batch-size 128 --learning-rate 0.1 --num-epochs 10 [--validate]

Models: mlp, logreg, cnn_3_layers, lenet, alexnet, vgg16, vgg19,
resnet18, resnet34, resnet50, rnn, lstm.  Falls back to synthetic data
when the dataset files are absent (no-egress environments).
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
from hetu_tpu import models

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("cnn")

MODELS = {
    "mlp": (models.mlp, "mnist"),
    "logreg": (models.logreg, "mnist"),
    "cnn_3_layers": (models.cnn_3_layers, "mnist"),
    "lenet": (models.lenet, "mnist"),
    "rnn": (models.rnn, "mnist"),
    "lstm": (models.lstm, "mnist"),
    "alexnet": (models.alexnet, "cifar"),
    "vgg16": (models.vgg16, "cifar"),
    "vgg19": (models.vgg19, "cifar"),
    "resnet18": (models.resnet18, "cifar"),
    "resnet34": (models.resnet34, "cifar"),
    "resnet50": (models.resnet50, "cifar"),
}


def load_dataset(kind, dataset):
    if kind == "mnist":
        tx, ty, vx, vy = ht.data.mnist(onehot=True)
        tx = tx.reshape(-1, 784)
        vx = vx.reshape(-1, 784)
    else:
        loader = ht.data.cifar100 if dataset == "CIFAR100" else ht.data.cifar10
        tx, ty, vx, vy = loader(onehot=True)
    return (tx.astype(np.float32), ty.astype(np.float32),
            vx.astype(np.float32), vy.astype(np.float32))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet18", choices=MODELS)
    parser.add_argument("--dataset", default=None)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--learning-rate", type=float, default=0.1)
    parser.add_argument("--num-epochs", type=int, default=1)
    parser.add_argument("--opt", default="sgd",
                        choices=["sgd", "momentum", "nesterov", "adagrad",
                                 "adam", "adamw", "lamb"])
    parser.add_argument("--validate", action="store_true")
    parser.add_argument("--comm-mode", default=None,
                        help="None / AllReduce / PS / Hybrid")
    args = parser.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    builder, kind = MODELS[args.model]
    tx, ty, vx, vy = load_dataset(kind, args.dataset)
    n_cls = ty.shape[-1]

    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    import inspect
    params = inspect.signature(builder).parameters
    if "num_class" in params:
        loss, y = builder(x, y_, num_class=n_cls)
    elif "dimoutput" in params:
        loss, y = builder(x, y_, dimoutput=n_cls)
    else:
        assert n_cls == 10, (
            f"{args.model} has a fixed 10-class head; got {n_cls} classes")
        loss, y = builder(x, y_)

    opts = {"sgd": ht.optim.SGDOptimizer,
            "momentum": ht.optim.MomentumOptimizer,
            "nesterov": lambda **kw: ht.optim.MomentumOptimizer(
                nesterov=True, **kw),
            "adagrad": ht.optim.AdaGradOptimizer,
            "adam": ht.optim.AdamOptimizer,
            "adamw": ht.optim.AdamWOptimizer,
            "lamb": ht.optim.LambOptimizer}
    opt = opts[args.opt](learning_rate=args.learning_rate)
    train_op = opt.minimize(loss)

    executor = ht.Executor({"train": [loss, y, train_op],
                            "validate": [loss, y]},
                           comm_mode=args.comm_mode)
    bs = args.batch_size
    n_train = (len(tx) // bs) * bs
    n_valid = (len(vx) // bs) * bs

    for epoch in range(args.num_epochs):
        t0 = time.time()
        train_loss, train_acc, nb = 0.0, 0.0, 0
        for i in range(0, n_train, bs):
            out = executor.run("train", feed_dict={
                x: tx[i:i + bs], y_: ty[i:i + bs]})
            train_loss += float(np.asarray(out[0]).reshape(-1)[0])
            pred = np.asarray(out[1])
            train_acc += float(
                (pred.argmax(-1) == ty[i:i + bs].argmax(-1)).mean())
            nb += 1
        dt = time.time() - t0
        logger.info(
            "epoch %d: loss=%.4f acc=%.4f (%.1f samples/s)", epoch,
            train_loss / nb, train_acc / nb, n_train / dt)
        if args.validate:
            v_loss, v_acc, vb = 0.0, 0.0, 0
            for i in range(0, n_valid, bs):
                out = executor.run("validate", feed_dict={
                    x: vx[i:i + bs], y_: vy[i:i + bs]})
                v_loss += float(np.asarray(out[0]).reshape(-1)[0])
                pred = np.asarray(out[1])
                v_acc += float(
                    (pred.argmax(-1) == vy[i:i + bs].argmax(-1)).mean())
                vb += 1
            logger.info("epoch %d: val_loss=%.4f val_acc=%.4f", epoch,
                        v_loss / vb, v_acc / vb)


if __name__ == "__main__":
    main()
