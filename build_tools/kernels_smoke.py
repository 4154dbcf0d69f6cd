"""Kernel push smoke: the ISSUE-10 acceptance gate, standalone on the
8-virtual-device CPU mesh.

Runs ``bench.kernels_aux`` (the ``bench.py --kernels`` capture) and
asserts:

- the batched CV grid over a packed matrix ran, and the round stats
  attribute the kernel_mode that ran it;
- the chunked weighted-gram satellite matches the unchunked scatter;
- int8/bfloat16 registration parity inside the documented 5e-2 bound
  (measured values are typically 100x tighter), int8/bf16 params
  actually smaller than f32, and live proba traffic within the bound;
- 0 post-warmup compiles across ALL THREE serve_dtype variants — each
  tier is its own prewarmed AOT program family.

Exit code 0 = pass. Usage:

    python build_tools/kernels_smoke.py [--quick]
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

#: the documented quantized-serving parity bound (also the registry's
#: registration gate default)
QUANT_BOUND = 5e-2


def main(quick=False):
    from bench import kernels_aux

    aux = kernels_aux(quick=quick)
    print(json.dumps({"kernels": aux}, indent=1))
    if "error" in aux:
        raise SystemExit(f"FAIL: kernels aux died: {aux['error']}")

    failures = []
    if aux["gram_chunked_max_diff"] > 1e-5:
        failures.append(
            f"chunked gram diff {aux['gram_chunked_max_diff']} > 1e-5"
        )
    if aux.get("kernel_mode") != "packed_gather":
        failures.append(
            f"kernel_mode attribution wrong: {aux.get('kernel_mode')}")

    sv = aux.get("serving_quant", {})
    for dt in ("int8", "bfloat16"):
        reg = sv.get(f"{dt}_registration_parity")
        live = sv.get(f"{dt}_proba_max_diff")
        if reg is None or reg > QUANT_BOUND:
            failures.append(f"{dt} registration parity {reg} > "
                            f"{QUANT_BOUND}")
        if live is None or live > QUANT_BOUND:
            failures.append(f"{dt} live proba diff {live} > {QUANT_BOUND}")
    f32_b = sv.get("float32_params_nbytes") or 0
    if not (sv.get("int8_params_nbytes", f32_b)
            < sv.get("bfloat16_params_nbytes", f32_b) < f32_b):
        failures.append(
            "quantized tiers did not shrink the staged params: "
            f"f32={f32_b} bf16={sv.get('bfloat16_params_nbytes')} "
            f"int8={sv.get('int8_params_nbytes')}"
        )
    delta = sv.get("postwarm_compile_delta", {})
    if any(delta.get(k_) for k_ in
           ("kernel_misses", "jit_misses", "aot_misses")):
        failures.append(
            f"compiles after warmup across dtype variants: {delta}"
        )
    if failures:
        raise SystemExit("FAIL: " + "; ".join(failures))
    print(
        f"PASS: chunked gram diff {aux['gram_chunked_max_diff']:.2e}, "
        "int8 parity "
        f"{sv.get('int8_registration_parity'):.2e} (bound {QUANT_BOUND}), "
        "0 post-warmup compiles across f32/bf16/int8"
    )


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
