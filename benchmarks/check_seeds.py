"""Read the two numbers each limit of a configuration's `check.limits` is
set from, on the chip, at the configuration's own size, in one process:

    python benchmarks/check_seeds.py --config <config> --seeds 12 --control 3

For each seed it builds the program's engine as a run does (no warm-up, no
server) and prints the numbers the configuration's family compares
(`families/<family>/compare.py` `readings`); for the first `--control`
seeds it also prints those of every control of the family
(`families/<family>/reference.py` `CONTROLS`: the reference with one stated
precision a step lower). The last lines sum up each number: the largest
sound reading, the smallest reading of each control and its ratio to it,
beside the limit the configuration file holds. Lines also go to
`chiprun_out/check_seeds_<config>.jsonl`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147483747)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args(argv)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from benchmarks import check, families, serve
    from distributed_lms_raft_llm_tpu.utils.compilation import (
        enable_compilation_cache,
    )

    enable_compilation_cache()
    if jax.devices()[0].platform != args.platform:
        print(f"no {args.platform}: {jax.devices()}", file=sys.stderr)
        return 3
    with open(os.path.join(HERE, "configs", args.config + ".json")) as fh:
        config = json.load(fh)
    fam = families.of_config(config)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    sound = []
    control = {c: [] for c in fam.reference.CONTROLS}
    with open(os.path.join(out_dir, f"check_seeds_{args.config}.jsonl"),
              "w") as log:

        def say(**doc):
            line = json.dumps(doc)
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()

        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            engine = serve.build_engine(config, seed)
            got = check.compare(engine.family, engine.cfg, engine.params,
                                config, seed)
            del engine
            sound.extend(got["readings"])
            say(seed=seed, sound=got["readings"])
            if i < args.control:
                seqs = check.sequences_of(config, seed)
                want = check.reference_side(config, seed, seqs)
                for name in fam.reference.CONTROLS:
                    ctl = check.reference_side(config, seed, seqs, name)
                    read = [fam.compare.readings(c, w)
                            for c, w in zip(ctl, want)]
                    del ctl
                    control[name].extend(read)
                    say(seed=seed, control=name, readings=read)
        for key, limit in config["check"]["limits"].items():
            lows = {c: min(r[key] for r in v)
                    for c, v in control.items() if v}
            top = max(r[key] for r in sound)
            say(number=key, config=args.config, family=fam.name,
                seeds=args.seeds, sound_max=top,
                sound_min=min(r[key] for r in sound), control_min=lows,
                limit=limit,
                ratios={c: v / top if top else None
                        for c, v in lows.items()},
                device=jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
