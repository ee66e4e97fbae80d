"""The control of the comparison that decides ``correct``: it has to fail.

The configurations state exact products over Z_2^32.  The control is the
plain reference put in the program's place and computed one ring width
down, over Z_2^16 (the 16-bit words a change might take to halve the
bytes on the wire), its results sign-extended back to 32-bit words.  Its
answers go through the same comparison as the program's, which has to
find them wrong.

Beside it, as readings and not as controls: the same products in float32
at the highest precision and in bfloat16 with float32 accumulation (the
MXU's default) on the device.  int8 operands with sums far below 2^24 are
exact in both, so those would pass the comparison, rightly: their answers
are the exact products.

    python perfbench/checks/control.py --workload <cell> --seeds 5 6 7

runs at the cell's own size (reference on the host, float readings on the
default JAX device) and prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def z16_control(ref, A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """The reference over Z_2^16, sign-extended to 32-bit words."""
    c = ref.matmul(A, B, 16).astype(np.uint16).view(np.int16)
    return c.astype(np.int32).view(np.uint32)


def float_reading(A: np.ndarray, B: np.ndarray, dtype: str) -> np.ndarray:
    """The product in a float type on the default JAX device, rounded
    back to 32-bit words."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(A[..., 0].view(np.int32).astype(np.float32))
    b = jnp.asarray(B[..., 0].view(np.int32).astype(np.float32))
    if dtype == "float32":
        c = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    else:
        c = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    out = np.asarray(c).astype(np.int64).astype(np.uint32)
    return out[..., None]


def readings(cell, seed: int, rehearse: bool = False,
             floats: bool = True) -> dict:
    """The numbers the comparison reads for the control (and the float
    readings) on every operand set of one seed, at the cell's size."""
    from perfbench import cells, harness, loadgen

    t, r, s = loadgen.shape(cell.config, cell.traffic, rehearse)
    B, As = loadgen.operands(seed, t, r, s, int(cell.traffic["operand_sets"]))
    ref = cells.reference(cell.config)
    e = int(cell.config["ring"]["e"])
    window = loadgen.Window(start=0.0, close=1.0, requests=[
        loadgen.Request(client=0, seq=k, operand=k, t_submit=0.0, t_done=1.0)
        for k in range(len(As))])
    t0 = time.perf_counter()
    expected = {k: ref.matmul(A, B, e) for k, A in enumerate(As)}
    out = {"seed": seed, "shape": [t, r, s], "sets": len(As),
           "reference_s": time.perf_counter() - t0}
    kinds = {"z16": lambda A: z16_control(ref, A, B)}
    if floats:
        kinds["float32"] = lambda A: float_reading(A, B, "float32")
        kinds["bfloat16"] = lambda A: float_reading(A, B, "bfloat16")
    for name, fn in kinds.items():
        for q in window.requests:
            q.result = fn(As[q.operand])
        got = harness.compare(window, expected)
        out[name] = {"wrong_elements": got["wrong_elements"],
                     "elements": sum(int(v.size) for v in expected.values()),
                     "correct": got["wrong_elements"] == 0}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import cells

    cell = cells.find_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name,
                          **readings(cell, seed, args.rehearse)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
