"""Read the numbers the limits of `correct` are set from: sound runs and
controls of one cell's checks, over many seeds in one process, no window.

    python3 benchmarks/controls.py --workload <cell> --seeds 1,2,3,... \
        [--he-scale-bits 26]

The readings are the configuration's check module's (`<path>/checks/
<check>.py`: `control_data`, `control_numbers`). For `image_classifier`, for
each seed, at the cell's own batch, clients and HE parameters:
`model_numbers` (the system's logits, loss and gradient on one batch against
the plain float32 reference) sound and with the reference computed in float8
in the system's place (the control: the nearest precision below the
configuration's bfloat16), and `train_numbers` (the check round against the
plain Adam reference), which also says what a skipped optimizer step would
read. With `--he-scale-bits` the first seed's readings are taken again at
each lower CKKS scale (the control of `he_avg_err`: fewer bits of plaintext
precision, the step a faster parameter set would take). The benchmark's own
runs never run this; `PERF.md` records what it read.
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

    if jax.default_backend() != "tpu":
        raise SystemExit("benchmarks/controls.py reads its numbers on the TPU")
    from hefl_tpu.utils.device import setup_compile_cache

    setup_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    cfg0 = run.build_config(cell, seeds[0], events_path="")
    check = run._module_at(cell["check"])
    scales = [2.0 ** int(b) for b in args.he_scale_bits.split(",") if b]
    rows, he_control = {}, {}  # by kind ("sound", a control's name): a seed each
    for seed in seeds:
        cfg = dataclasses.replace(cfg0, seed=seed)
        data = check.control_data(cfg)
        got = check.control_numbers(cell, cfg, data)
        for kind, numbers in got.items():
            rows.setdefault(kind, []).append(numbers)
        run.say(seed=seed, **got)
        for scale in scales if seed == seeds[0] else ():
            c = dataclasses.replace(cfg, he=dataclasses.replace(cfg.he, scale=scale))
            he_control[str(scale)] = check.control_numbers(
                cell, c, data)["sound"]["he_avg_err"]
    sound = rows.pop("sound")
    summary = {name: {"sound_max": max(s[name] for s in sound),
                      "sound_min": min(s[name] for s in sound),
                      "sound_all": [s[name] for s in sound]}
               for name in sound[0]}
    for kind, control in rows.items():
        for name in control[0]:
            summary[name][kind + "_min"] = min(c[name] for c in control)
    if he_control:
        summary["he_avg_err"]["control_at_scale"] = he_control
    print(json.dumps({"workload": args.workload, "seeds": seeds, **summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
