"""The control of the comparison that decides `correct`: the model
module's reference, one precision lower (int4, the configuration states
int8), put in the program's place.  It has to come out as not correct.

    python3 benchmarks/chip/control.py --workload mnist_L.backlog \
        --seeds 11,12,13

Per seed it makes what a run of the cell makes from that seed (weights
on the device, calibration images, the request pool), answers every
pool image with the int4 reference and compares those answers with the
int8 reference exactly as a run compares the program's.  One JSON line
per seed with the numbers compared and their limits.
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from benchmarks.chip import bench, harness, images, traffic  # noqa: E402


CONTROL_BITS = 4            # one precision below the stated int8


def control_numbers(config: dict, mix: dict, seed: int) -> dict:
    import jax
    model = bench.model(config)
    g = config["geometry"]
    rngs = traffic.streams(seed)
    params = jax.device_get(model.make_params(g, rngs["weights"]))
    calib = images.make_images(config["images"], g["input_shape"],
                               config["calib_n"], rngs["calib"])
    pool = images.make_images(config["images"], g["input_shape"],
                              mix["pool"], rngs["pool"])
    v8, p8 = model.reference(g, params, calib, pool, 8)
    v, p = model.reference(g, params, calib, pool, CONTROL_BITS)
    return {"vq_mismatch": int(np.sum(v != v8)),
            "pred_mismatch": int(np.sum(p != p8)),
            "answers": int(len(pool))}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = bench.resolve(bench.load(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control_numbers(spec["config"], spec["mix"], seed)
        correct = all(nums[k] <= harness.LIMITS[k] for k in nums
                      if k in harness.LIMITS)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "bits": CONTROL_BITS, "correct": correct, **nums,
                          "limits": harness.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
