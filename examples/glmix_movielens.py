"""BASELINE configs 3+4: GLMix — fixed effect + per-user + per-item random
effects with full coordinate descent (>= 2 sweeps).

This environment has no network access, so the data is a MovieLens-1M-SHAPED
synthetic (documented honestly as such): 100k interactions, 6,040 users,
3,706 items — the real dataset's entity counts — with a planted
fixed+user+item GLMix structure generated by the library's test generators.
Swap ``--n`` / the generator for the real MovieLens ratings to reproduce on
the actual dataset.

Runs the REAL GAME training CLI (cli.train) end-to-end: Avro write -> index
-> fixed + 2 random-effect coordinates, 2 CD sweeps -> AUC/logistic loss.

FETCH-FREE BY DEFAULT: the training loop runs without per-update validation
evaluation (each per-update eval is a device->host score fetch + metric
sweep, so a first-contact run would time the fetches instead of the
solves). Quality metrics come from ONE scoring pass after the timed
training wall. ``--verbose`` restores per-update eval + INFO logging for
debugging convergence.

The MEASURED shape of this example is the benchmark configuration
``benchmark/configs/glmix-user-item-1chip.json`` (cell
``glmix-user-item-1chip.fit-3coord``: the same three coordinates and update
sequence at d 1024, d_re 32, 1.31M rows on one TPU v5e chip; numbers in
PERF.md). The wall this script prints is the wall of whatever backend it ran
on (``backend`` in its result line) with one-time builds and compiles inside
it: a smoke observation, never a device metric.

Run:  python examples/glmix_movielens.py [--out out-glmix] [--n 100000]
"""

import argparse
import json
import os
import sys
import tempfile
import time

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from photon_ml_tpu.utils.compile_cache import enable_persistent_compilation_cache

enable_persistent_compilation_cache()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="out-glmix")
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--users", type=int, default=6040)
    ap.add_argument("--items", type=int, default=3706)
    ap.add_argument("--cd-iterations", type=int, default=2)
    ap.add_argument(
        "--verbose",
        action="store_true",
        help="per-update validation eval + INFO logging (adds a score fetch "
        "and metric sweep after every coordinate update; default is the "
        "fetch-free timed path)",
    )
    args = ap.parse_args()

    from photon_ml_tpu.cli import score, train
    from photon_ml_tpu.io import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_AVRO
    from photon_ml_tpu.testing import generate_mixed_effect_data
    from photon_ml_tpu.testing import generate_game_records

    print(f"generating MovieLens-1M-shaped GLMix data: n={args.n}", file=sys.stderr)
    data = generate_mixed_effect_data(
        n=args.n,
        d_fixed=16,
        re_specs={"userId": (args.users, 8), "itemId": (args.items, 8)},
        seed=42,
    )
    recs = generate_game_records(data)
    n_val = args.n // 5
    tmp = tempfile.mkdtemp(prefix="glmix_movielens_")
    train_p = os.path.join(tmp, "train.avro")
    val_p = os.path.join(tmp, "val.avro")
    schema = {
        **TRAINING_EXAMPLE_AVRO,
        "fields": TRAINING_EXAMPLE_AVRO["fields"]
        + [
            {
                "name": bag,
                "type": {"type": "array", "items": "FeatureAvro"},
                "default": [],
            }
            for bag in ("userFeatures", "itemFeatures")
        ],
    }
    write_avro_file(train_p, schema, recs[n_val:])
    write_avro_file(val_p, schema, recs[:n_val])

    shard_args = [
        "--feature-shard", "name=globalShard,bags=features",
        "--feature-shard", "name=userShard,bags=userFeatures",
        "--feature-shard", "name=itemShard,bags=itemFeatures",
    ]
    train_args = [
        "--input-data", train_p,
        "--task", "logistic_regression",
        *shard_args,
        "--coordinate",
        "name=global,shard=globalShard,optimizer=TRON,reg.type=L2,reg.weights=1",
        "--coordinate",
        "name=per-user,shard=userShard,re.type=userId,optimizer=LBFGS,"
        "reg.type=L2,reg.weights=1",
        "--coordinate",
        "name=per-item,shard=itemShard,re.type=itemId,optimizer=LBFGS,"
        "reg.type=L2,reg.weights=1",
        "--coordinate-descent-iterations", str(args.cd_iterations),
        "--output-dir", args.out,
    ]
    if args.verbose:
        # debugging mode: per-update validation eval + INFO logs (each eval
        # fetches the score vector and sweeps the metric suite)
        train_args += [
            "--validation-data", val_p,
            "--evaluators", "AUC,LOGISTIC_LOSS",
            "--log-level", "INFO",
        ]
    else:
        train_args += ["--log-level", "WARNING"]

    t0 = time.time()
    summary = train.run(train_args)
    wall = time.time() - t0
    n_train = args.n - n_val

    if args.verbose:
        metrics = summary["best"]["metrics"]
    else:
        # quality check OUTSIDE the timed window: one scoring pass over the
        # held-out fifth (the only deliberate device->host score fetch)
        _, evaluation = score.run(
            [
                "--input-data", val_p,
                *shard_args,
                "--id-tags", "userId,itemId",
                "--model-input-dir", os.path.join(args.out, "models", "best"),
                "--task", "logistic_regression",
                "--evaluators", "AUC,LOGISTIC_LOSS",
                "--output-dir", os.path.join(tmp, "scores"),
                "--log-level", "WARNING",
            ]
        )
        metrics = evaluation.metrics
    # CD iteration 0 pays every one-time cost (RE dataset build, XLA
    # compilation of each entity-bucket shape); steady-state sweeps
    # (iter >= 1) are the per-coordinate log timestamps after it
    result = {
        "config": "glmix-movielens-shaped-2re",
        "auc": metrics["AUC"],
        "logistic_loss": metrics["LOGISTIC_LOSS"],
        "n_train": n_train,
        "users": args.users,
        "items": args.items,
        "cd_sweeps": args.cd_iterations,
        # of this run on this backend, builds and compiles included
        "backend": jax.default_backend(),
        "wall_clock_s": round(wall, 2),
    }
    print(json.dumps(result))
    # random effects must add signal over the fixed effect alone
    assert result["auc"] > 0.72, result
    return result


if __name__ == "__main__":
    main()
