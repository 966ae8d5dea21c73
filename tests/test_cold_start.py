"""Cold-start story: a restarted validator must not re-pay kernel
compilation — the persistent compile cache turns the second process's
warmup into a load.

Reference analog: no lazy work on the consensus path; a stellar-core
restart is serving envelopes as soon as state is restored. Here the
equivalent hazard is XLA compilation, so TpuSigVerifier.warmup() over the
compile cache placed by the one rule (parallel/device.py: where
JAX_COMPILATION_CACHE_DIR points, else `<repo>/.jax_cache`) must turn a
restart into a cache load. What a CPU run can say about that is counts
and classes, not seconds: how long a load takes is the chip's to answer
(chip_smoke.py prints it per shape).
"""

import json
import os
import subprocess
import sys

_CHILD = r"""
import json, os
from stellar_core_tpu.parallel.device import (
    compile_cache_entries, configure_compile_cache)
cache = configure_compile_cache()
assert cache == os.environ["SCT_TEST_CACHE"], cache
from stellar_core_tpu.crypto.batch_verifier import (
    TpuSigVerifier, VerifierContext, VerifierStats)
from stellar_core_tpu.crypto.keys import SecretKey
before = compile_cache_entries(cache)
v = TpuSigVerifier(VerifierContext(stats=VerifierStats()))
v.BUCKETS = (32,)
v.warmup(wait=True)
sk = SecretKey.from_seed(b"\x31" * 32)
assert v.verify_many([(sk.public_key.key_bytes, sk.sign(b"m"), b"m")]) \
    == [True]
j = v.ctx.stats.to_json()
print("COLD_JSON " + json.dumps(
    {"cache": j["warmup"]["buckets"]["32"]["cache"],
     "dir": j["compile_cache"]["dir"],
     "before": before, "after": compile_cache_entries(cache)}))
"""


def _run_node(cache_dir: str) -> dict:
    env = dict(os.environ)
    env["SCT_TEST_CACHE"] = env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    r = subprocess.run([sys.executable, "-c", _CHILD],
                       capture_output=True, text=True, timeout=900,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    for line in r.stdout.splitlines():
        if line.startswith("COLD_JSON "):
            return json.loads(line[10:])
    raise AssertionError("no COLD_JSON in output: %s" % r.stdout[-300:])


def test_restart_compiles_from_cache(tmp_path):
    """The first process compiles the kernel and writes it to the cache
    the environment named; the second adds no entry and its warmup
    classifies the shape `hit`."""
    cache = str(tmp_path / "xla-cache")
    cold = _run_node(cache)
    assert cold["dir"] == cache
    assert cold["cache"] == "miss", cold
    assert cold["before"] == 0 and cold["after"] > 0, cold
    warm = _run_node(cache)
    assert warm["cache"] == "hit", warm
    assert warm["before"] == warm["after"] == cold["after"], (cold, warm)
