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
(the kind asks for the text after the window has closed).
"""

import argparse
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", required=True)
    args, rest = ap.parse_known_args(argv)

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
                rec = {"tag": args.tag, "argv": rest,
                       "sha256": hashlib.sha256(text.encode()).hexdigest(),
                       "chars": len(text)}
                sys.stderr.write(f"model digest {json.dumps(rec)}\n")
                out = os.path.join(ROOT, "chiprun_out")
                os.makedirs(out, exist_ok=True)
                with open(os.path.join(
                        out, f"model_digest.{args.tag}.json"), "w") as f:
                    json.dump(rec, f)
                return text

            lgb.Booster.model_to_string = digesting
        return load_plugin(folder, name)

    bench_run.load_plugin = load_and_wrap
    return bench_run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
