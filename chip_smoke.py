"""Serve SmolLM-360M at full width on a TPU through the normal entry point.

    python chip_smoke.py               # one chip: four serving phases
    python chip_smoke.py --four-chips  # sharded serving: mesh (1, 4) vs one chip

Every phase is one in-process call of ``repro.launch.serve.main`` on
``--full --arch smollm-360m`` (32 layers, d_model 960, vocab 49152; random
weights from seed 0), serving 8 requests of 256 prompt tokens and 16 greedy
new tokens each from a 2048-token cache:

  a  ``--quant float``
  b  ``--quant abfp-packed --gain 1.0``
  c  ``--fused --gain 1.0``; its tokens must equal a packed engine's at
     gain 1.0 with the same int8 KV cache (the fused decode kernels
     compute the packed chain's numbers)
  d  ``--fused --gain 8.0 --overlap``; its tokens must equal the same run
     without ``--overlap``

``--four-chips`` runs phases b and c at mesh (1, 4) and on one device and
requires identical tokens, and that the column-parallel packed weights
really span the four devices.

Each phase checks that every request completed with ``max_new`` tokens
inside the vocabulary, and prints its compile and serve seconds and the
number of Pallas kernels (``tpu_custom_call``) in its compiled decode step:
zero would mean the ABFP kernels did not compile for the chip.  The last
line of output is the JSON result; the script exits non-zero, and prints
no result, when JAX finds no TPU or any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import re
import sys
import traceback
from pathlib import Path

import jax

ARCH = "smollm-360m"
REQUESTS, MAX_NEW = 8, 16
COMMON = ["--full", "--arch", ARCH, "--capacity", "8", "--max-len", "2048",
          "--prompt-len", "256", "--max-new", str(MAX_NEW),
          "--requests", str(REQUESTS), "--prefill-chunks", "64"]


def kernel_count(engine) -> int:
    """Pallas kernels compiled into the engine's decode step."""
    return len(re.findall(r'custom_call_target="tpu_custom_call"',
                          engine.compiled_hlo(("decode",))))


def tokens_of(requests) -> dict:
    return {r.uid: list(r.generated) for r in requests}


def check_served(requests, vocab: int) -> list:
    """Every request completed, with MAX_NEW tokens inside the vocabulary."""
    errors = []
    if sorted(r.uid for r in requests) != list(range(REQUESTS)):
        errors.append(f"completed {sorted(r.uid for r in requests)}, "
                      f"expected uids 0..{REQUESTS - 1}")
    for r in requests:
        if len(r.generated) != MAX_NEW:
            errors.append(f"req {r.uid}: {len(r.generated)} tokens")
        if any(not 0 <= t < vocab for t in r.generated):
            errors.append(f"req {r.uid}: token outside [0, {vocab})")
    return errors


def serve(name: str, argv: list, *, kernels: bool) -> tuple:
    """One phase through the entry point: (what was served, errors)."""
    from repro.launch.serve import main

    served = main(COMMON + argv)
    eng = served.engine
    errors = check_served(served.requests, eng.mcfg.vocab_size)
    n_kernels = kernel_count(eng)
    if kernels and n_kernels == 0:
        errors.append("no tpu_custom_call in the decode step")
    print("phase " + json.dumps({
        "phase": name, "argv": argv, "compile_s": served.compile_s,
        "serve_s": served.serve_s,
        "tokens": sum(len(r.generated) for r in served.requests),
        "tpu_custom_calls": n_kernels, "errors": errors}), flush=True)
    return served, errors


def packed_reference(fused) -> dict:
    """Greedy tokens of an abfp_packed engine configured as the ``--fused``
    run ``fused`` was (same weights, gain, int8 KV cache, buckets, seed),
    on the same prompts."""
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    f = fused.engine
    eng = ServingEngine(init_params(jax.random.PRNGKey(f.seed), f.mcfg),
                        f.mcfg, capacity=f.capacity, max_len=f.max_len,
                        quant=dataclasses.replace(f.quant, mode="abfp_packed"),
                        seed=f.seed, prefill_chunks=f.prefill_chunks)
    done = eng.run([Request(uid=r.uid, prompt=list(r.prompt),
                            max_new_tokens=MAX_NEW) for r in fused.requests])
    return tokens_of(done)


def compare(name: str, got: dict, want: dict) -> list:
    """Greedy tokens equal; else report where each request first differs."""
    first = {uid: next((i for i, (a, b) in enumerate(
                            zip(got.get(uid, []), want[uid])) if a != b), None)
             for uid in sorted(want) if got.get(uid) != want[uid]}
    print("phase " + json.dumps({"phase": name, "tokens_equal": not first,
                                 "first_difference": first}), flush=True)
    return [f"{name}: greedy tokens differ"] if first else []


def one_chip() -> list:
    errors = []

    def run(fn):
        # A phase that raises is recorded and the rest still run, so one
        # chip call shows every phase's outcome.
        try:
            return fn()
        except Exception:  # noqa: BLE001 — reported, then the run fails
            traceback.print_exc()
            errors.append(f"{fn.__name__} raised")
            return None
        finally:
            gc.collect()

    def phase_a():
        errors.extend(serve("a float", ["--quant", "float"],
                            kernels=False)[1])

    def phase_b():
        errors.extend(serve("b abfp-packed", ["--quant", "abfp-packed",
                                              "--gain", "1.0"],
                            kernels=True)[1])

    def phase_c():
        fused, errs = serve("c fused", ["--fused", "--gain", "1.0"],
                            kernels=True)
        errors.extend(errs)
        errors.extend(compare("c fused == packed with int8 KV",
                              tokens_of(fused.requests),
                              packed_reference(fused)))

    def phase_d():
        got, errs = serve("d fused overlap",
                          ["--fused", "--gain", "8.0", "--overlap"],
                          kernels=True)
        errors.extend(errs)
        ref, errs = serve("d fused blocking", ["--fused", "--gain", "8.0"],
                          kernels=True)
        errors.extend(errs)
        errors.extend(compare("d overlap == blocking",
                              tokens_of(got.requests),
                              tokens_of(ref.requests)))

    for fn in (phase_a, phase_b, phase_c, phase_d):
        run(fn)
    return errors


def spans_devices(params, n: int) -> list:
    """The 960- and 2560-column packed weights are column-sharded over all
    ``n`` devices (320-column K/V projections stay replicated by design)."""
    from repro.core.abfp import PackedWeight

    errors, seen = [], 0
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, PackedWeight))
    for pw in leaves:
        if not isinstance(pw, PackedWeight) or pw.n_cols not in (960, 2560):
            continue
        seen += 1
        shards = pw.codes.addressable_shards
        devices = {s.device for s in shards}
        widths = {s.data.shape[-1] for s in shards}
        if len(devices) != n or widths != {pw.codes.shape[-1] // n}:
            errors.append(f"packed ({pw.k}, {pw.n_cols}) weight: shards on "
                          f"{len(devices)} devices, widths {sorted(widths)}")
    if seen == 0:
        errors.append("no 960- or 2560-column packed weight found")
    return errors


def four_chips() -> list:
    if len(jax.devices()) < 4:
        return [f"--four-chips needs 4 devices, found {len(jax.devices())}"]
    errors = []
    for name, argv in (("b abfp-packed", ["--quant", "abfp-packed",
                                          "--gain", "1.0"]),
                       ("c fused", ["--fused", "--gain", "1.0"])):
        one, errs = serve(f"{name} one device", argv, kernels=True)
        errors.extend(errs)
        one = tokens_of(one.requests)
        gc.collect()
        mesh, errs = serve(f"{name} mesh 1x4", argv + ["--mesh", "1,4"],
                           kernels=True)
        errors.extend(errs)
        errors.extend(spans_devices(mesh.engine.params, 4))
        errors.extend(compare(f"{name} mesh 1x4 == one device",
                              tokens_of(mesh.requests), one))
        del mesh
        gc.collect()
    return errors


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path: mesh (1, 4) vs one chip")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    errors = four_chips() if args.four_chips else one_chip()
    if errors:
        print("chip_smoke FAILED:\n  " + "\n  ".join(errors), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
