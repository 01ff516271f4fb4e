"""Read the numbers the limits of `correct` are set from: sound runs and
controls of one cell's checks, over many seeds in one process, no window.

    python3 benchmarks/controls.py --workload <cell> --seeds 1,2,3,... \
        [--he-scale-bits 26]

For each seed, at the cell's own batch, clients and HE parameters:
`run.model_numbers` (the system's logits, loss and gradient on one batch
against the plain float32 reference) sound and with the reference computed
in float8 in the system's place (the control: the nearest precision below
the configuration's bfloat16), and `run.train_numbers` (the check round
against the plain Adam reference), which also says what a skipped optimizer
step would read. With `--he-scale-bits` the first seed's check round is run
again at each lower CKKS scale (the control of `he_avg_err`: fewer bits of
plaintext precision, the step a faster parameter set would take). The
benchmark's own runs never run this; `PERF.md` records what it read.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(_HERE), _HERE]

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--he-scale-bits", default="")
    ap.add_argument("--benchmark", default=os.path.join(run.ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    cell = run.load_cell(args.benchmark, args.workload)
    for key, val in cell["config"].get("env", {}).items():
        os.environ[key] = str(val)
    import jax
    import numpy as np

    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/controls.py reads its numbers on the TPU")
    from hefl_tpu.data import make_dataset
    from hefl_tpu.models import create_model
    from hefl_tpu.utils.device import setup_compile_cache

    setup_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    cfg0 = run.build_config(cell, seeds[0], events_path="")
    find = lambda name: run._module_at(  # noqa: E731
        run._find(cell["paths"], "reference", name + ".py"))
    ref, adam = find(cell["config"]["reference"]), find("adam")
    bs, classes = cfg0.train.batch_size, cfg0.train.num_classes
    n_train = cfg0.num_clients * 2 * (run.CHECK_STEPS + 1) * bs
    scales = [2.0 ** int(b) for b in args.he_scale_bits.split(",") if b]
    rows = {"sound": [], "control_fp8": [], "he_control": {}}
    for seed in seeds:
        cfg = dataclasses.replace(cfg0, seed=seed)
        (x, y), _, _ = make_dataset(cfg.dataset, seed=seed, n_train=n_train,
                                    n_test=2)
        module, _ = create_model(cfg.model, num_classes=classes,
                                 input_shape=tuple(int(d) for d in x.shape[1:]))
        xb = np.asarray(x[:bs], np.float32) / 255.0
        onehot = np.eye(classes, dtype=np.float32)[y[:bs]]
        sound = run.model_numbers(module, ref, xb, onehot, seed)
        sound.update(run.train_numbers(cfg, module, ref, adam, x, y))
        rows["sound"].append(sound)
        rows["control_fp8"].append(run.model_numbers(
            module, ref, xb, onehot, seed, quant=run.fp8_quant))
        run.say(seed=seed, sound=sound, control_fp8=rows["control_fp8"][-1])
        for scale in scales if seed == seeds[0] else ():
            c = dataclasses.replace(cfg, he=dataclasses.replace(cfg.he, scale=scale))
            got = run.train_numbers(c, module, ref, adam, x, y)["he_avg_err"]
            rows["he_control"][str(scale)] = got
    summary = {name: {"sound_max": max(s[name] for s in rows["sound"]),
                      "sound_all": [s[name] for s in rows["sound"]]}
               for name in rows["sound"][0]}
    for name in rows["control_fp8"][0]:
        summary[name]["control_fp8_min"] = min(
            c[name] for c in rows["control_fp8"])
    for name in ("skipped_step_reads", "untrained_val_reads"):
        summary[name]["sound_min"] = min(s[name] for s in rows["sound"])
    summary["he_avg_err"]["control_at_scale"] = rows["he_control"]
    print(json.dumps({"workload": args.workload, "seeds": seeds, **summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
