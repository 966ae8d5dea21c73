"""A kernel's share of its roofline over the traced slice: the least
time the chip could take for the signatures really verified there
(benchmark/harness/work.py; padding lanes are not work; peaks from
harness/peaks.json by device_kind) over the device time of the verify
executable in the trace. Returns nothing where the kernel did not run
in the slice; raises on a device_kind the table lacks.

args: kernel   label of the module group the reduction summed"""

from ..harness import work


def read(ctx: dict, args: dict):
    kernel_s = ctx["trace"]["kernels_s"].get(args["kernel"], 0.0)
    sigs = ctx["slice_counts"]["sigs"]
    on_chip = ctx["platform"] == "tpu"
    if on_chip:
        work.peaks(ctx["device_kind"])      # an unknown kind is an error
    if kernel_s <= 0 or sigs <= 0:
        return None
    if not on_chip:         # the CPU rehearsal: shape only, never printed
        return 100.0 * 1e-9
    r = work.ed25519_roofline(sigs, kernel_s, ctx["device_kind"])
    print("[bench] ed25519 roofline: %d signatures in %.6f s of kernel "
          "time, least %.9f s, bound by %s" % (
              sigs, kernel_s, r["least_s"], r["bound"]), flush=True)
    return r["pct"]
