"""End-to-end driver: train a ResNet classifier for a few hundred steps,
dense vs ssProp (2-epoch bar @ 80%), reproducing the paper's protocol at
laptop scale: same optimizer (Adam 2e-4), Kaiming init, no augmentation.

Prints per-epoch train loss / eval accuracy for both modes plus the
backward-FLOPs ledger. ~100M-param variant available via --model
resnet50 --image-size 32.

Run:  PYTHONPATH=src python examples/train_classifier.py --steps 300
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.core.policy import SsPropPolicy, paper_default
from repro.core.schedulers import average_rate, drop_rate_for_step
from repro.data.pipeline import ImagePipeline, ImagePipelineConfig
from repro.launch import steps
from repro.models import resnet
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18", choices=list(resnet.LAYOUTS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--steps-per-epoch", type=int, default=50)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--lr", type=float, default=2e-4)  # paper Table 2
    ap.add_argument("--drop-rate", type=float, default=0.8)
    args = ap.parse_args()

    image = (3, args.image_size, args.image_size)
    pipe = ImagePipeline(
        ImagePipelineConfig(image, args.classes, args.batch, seed=11), n_train=1024
    )
    ocfg = adam.AdamConfig(lr=args.lr)

    def build(policy_rate_fn, seed=0):
        params = resnet.init_params(args.model, jax.random.PRNGKey(seed), args.classes)
        opt = adam.init(params)
        jits = {}

        def get(rate):
            if rate not in jits:
                pol = paper_default(rate) if rate > 0 else SsPropPolicy(0.0)
                jits[rate] = jax.jit(steps.make_classifier_step(args.model, pol, ocfg))
            return jits[rate]

        return params, opt, get

    results = {}
    for mode in ("dense", "ssprop"):
        rate_fn = (
            (lambda i: 0.0)
            if mode == "dense"
            else lambda i: drop_rate_for_step(
                "epoch_bar", step=i, steps_per_epoch=args.steps_per_epoch,
                total_steps=args.steps, target=args.drop_rate,
            )
        )
        params, opt, get = build(rate_fn)
        t0 = time.time()
        for i in range(args.steps):
            b = jax.tree.map(jnp.asarray, pipe.batch_at(i))
            params, opt, loss = get(rate_fn(i))(params, opt, b["images"], b["labels"])
            if (i + 1) % args.steps_per_epoch == 0:
                ev = pipe.eval_batch(256)
                logits = resnet.forward(
                    args.model, params, jnp.asarray(ev["images"]),
                    SsPropPolicy(0.0), train=False,
                )
                acc = float((jnp.argmax(logits, -1) == jnp.asarray(ev["labels"])).mean())
                print(f"[{mode}] step {i+1:4d} loss={float(loss):.4f} eval_acc={acc:.3f}")
        results[mode] = (time.time() - t0, acc)

    avg = average_rate(
        "epoch_bar", total_steps=args.steps,
        steps_per_epoch=args.steps_per_epoch, target=args.drop_rate,
    )
    d, _ = resnet.flops_per_iter(args.model, args.batch, image)
    _, s = resnet.flops_per_iter(args.model, args.batch, image, avg)
    print(f"\nbackward FLOPs/iter: dense {d/1e9:.2f}B -> ssprop {s/1e9:.2f}B "
          f"({100*(1-s/d):.1f}% saved at schedule-average rate {avg:.2f})")
    for mode, (t, acc) in results.items():
        print(f"{mode:7s} wall={t:.1f}s final_eval_acc={acc:.3f}")


if __name__ == "__main__":
    main()
