#!/usr/bin/env python3
"""python3 benchmark/control.py --workload <cell> --seeds a,b,c --seconds <s> --control <name>

The controls and planted faults of "how `correct` is decided": each
drives a cell through the same code as run.py with one guarantee of its
configuration broken, and has to come out with `correct` false. Not a
benchmark run: the driver never calls it. All seeds run in one process
(one JAX start, one warm-up). Exit code 0 where every seed came out not
correct, 1 where one came out correct.

  cpu-backend       the node under test verifies on the CPU (the step
                    that would tempt a later PR where the host is
                    faster): breaks "nothing falls back to the CPU"
  accept-all        the device verifier answers True for every lane and
                    never runs the kernel: an answer altered where it is
                    produced; breaks "a corrupted signature is refused"
                    and "every signature is verified on the device"
  half-batch        the device verifies the first half of every batch
                    and answers True for the rest: half of the batch
                    left out
  tampered-archive  one byte of an archived transaction set is flipped
                    after publishing: an answer altered where it is
                    produced, on the history-verify side (catchup only)

On the CPU (benchmark/tests) the same functions run at a tiny size.
"""

import argparse
import glob
import gzip
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_verifier(app):
    return getattr(app.sig_verifier, "inner", app.sig_verifier)


def accept_all(app) -> None:
    v = _device_verifier(app)
    if getattr(v, "name", "") == "tpu":
        v.verify_many = lambda triples: [True] * len(triples)


def half_batch(app) -> None:
    v = _device_verifier(app)
    if getattr(v, "name", "") != "tpu":
        return
    orig = v.verify_many

    def verify_many(triples):
        keep = (len(triples) + 1) // 2
        return orig(triples[:keep]) + [True] * (len(triples) - keep)

    v.verify_many = verify_many


def tamper_archive(dep) -> None:
    files = sorted(glob.glob(os.path.join(
        dep.hist.archive_root, "**", "transactions-*.xdr.gz"),
        recursive=True))
    if not files:
        raise RuntimeError("no archived transaction set to tamper with")
    with gzip.open(files[-1], "rb") as fh:
        body = bytearray(fh.read())
    body[len(body) // 2] ^= 0x01
    with gzip.open(files[-1], "wb") as fh:
        fh.write(bytes(body))


CONTROLS = {
    "cpu-backend": {"backend_under_test": "cpu"},
    "accept-all": {"node_hook": accept_all},
    "half-batch": {"node_hook": half_batch},
    "tampered-archive": {"after_setup": tamper_archive},
}


def chain(*hooks):
    hooks = [h for h in hooks if h is not None]

    def run(app) -> None:
        for h in hooks:
            h(app)

    return run if hooks else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("benchmark/control.py needs a TPU", file=sys.stderr)
        return 2
    from stellar_core_tpu.parallel.device import configure_compile_cache
    configure_compile_cache()
    import logging
    from stellar_core_tpu.util.log import init_logging
    init_logging(logging.ERROR)
    from benchmark.harness import runner
    from benchmark.harness.manifest import Manifest
    manifest = Manifest(ROOT)
    came_out_correct = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            res = runner.run_cell(manifest, args.workload, seed,
                                  args.seconds, False, t0,
                                  **CONTROLS[args.control])
        except Exception as e:     # a control that crashes has failed too
            print("CONTROL %s %s seed %d: no result (%s: %s) -> not correct"
                  % (args.control, args.workload, seed,
                     type(e).__name__, e), flush=True)
            continue
        line = res["line"]
        came_out_correct += bool(line["correct"])
        print("CONTROL %s %s seed %d: correct=%s compared=%s" % (
            args.control, args.workload, seed, line["correct"],
            json.dumps(line["compared"])), flush=True)
    return 1 if came_out_correct else 0


if __name__ == "__main__":
    sys.exit(main())
