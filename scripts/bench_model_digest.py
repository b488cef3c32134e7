#!/usr/bin/env python3
"""One benchmark run that also says which model it trained.

``python3 scripts/bench_model_digest.py --tag <name> --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` is ``benchmark/run.py`` with the
same arguments (its result is still the last line of standard output),
and writes the SHA-256 and length of the text the kind's one
``Booster.model_to_string()`` call returns to standard error and to
``chiprun_out/model_digest.<tag>.json``.  Two commits that print the same
digest at the same seed trained byte-identical models at the cell's full
size; no file of the harness is edited and the timed window is untouched
(the kind asks for the text after the window has closed).  A kind that
reads its trees through ``Booster.dump_model()`` (the multiclass cell's)
records instead the SHA-256 of the dump's JSON and of each tree's in
``tree_info`` order, under ``tree_sha256`` in the file (stderr gets the
count and the dump's digest): two runs of unequal windows agree tree by
tree on the trees both grew.  After the run it adds what the growth
programs were built with: the ``grow.*`` and ``shard.*`` gauges
(``grow.hist_cols``, ``grow.wave_width``, the plan probes'
``grow.fused.w<W>_ms`` where they ran) and each cached
``GrowerPrograms``' row bucket, stat columns, stage plan and its source.
"""

import argparse
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def programs_built() -> dict:
    """Gauges and stage plans of the growth programs this process built
    (attributes an older tree lacks are left out)."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.ops import grow

    gauges = obs.registry().snapshot()["gauges"]
    fields = ("num_data", "hist_cols", "wave_width", "stage_plan",
              "plan_source")
    return {"gauges": {k: v for k, v in sorted(gauges.items())
                       if k.startswith(("grow.", "shard."))},
            "programs": [{f: getattr(p, f) for f in fields
                          if hasattr(p, f)}
                         for p in grow._PROGRAM_CACHE.values()]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", required=True)
    args, rest = ap.parse_known_args(argv)
    path = os.path.join(ROOT, "chiprun_out",
                        f"model_digest.{args.tag}.json")
    # a kind that reads its trees through dump_model leaves no digest
    rec = {"tag": args.tag, "argv": rest}

    def write():
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(rec, f)

    from benchmark import run as bench_run

    load_plugin = bench_run.load_plugin

    def load_and_wrap(folder, name):
        # the kind loads after the configuration's environment is applied
        # and JAX has started: only now may the program be imported
        if folder == "kinds":
            import lightgbm_tpu as lgb
            to_string = lgb.Booster.model_to_string

            def digesting(self, *a, **kw):
                text = to_string(self, *a, **kw)
                rec.update(
                    sha256=hashlib.sha256(text.encode()).hexdigest(),
                    chars=len(text))
                sys.stderr.write(f"model digest {json.dumps(rec)}\n")
                write()
                return text

            lgb.Booster.model_to_string = digesting
            dump = lgb.Booster.dump_model

            def dump_digesting(self, *a, **kw):
                model = dump(self, *a, **kw)
                sha = lambda o: hashlib.sha256(json.dumps(
                    o, sort_keys=True).encode()).hexdigest()
                trees = [sha(t) for t in model.get("tree_info", [])]
                rec.update(dump_sha256=sha(model), trees=len(trees))
                sys.stderr.write(f"model dump digest {json.dumps(rec)}\n")
                rec["tree_sha256"] = trees
                write()
                return model

            lgb.Booster.dump_model = dump_digesting
        return load_plugin(folder, name)

    bench_run.load_plugin = load_and_wrap
    rc = bench_run.main(rest)
    rec.update(programs_built())
    shown = {k: v for k, v in rec.items() if k != "tree_sha256"}
    sys.stderr.write(f"programs built {json.dumps(shown)}\n")
    write()
    return rc


if __name__ == "__main__":
    sys.exit(main())
