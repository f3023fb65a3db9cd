"""Size of the lowered DPFL round_step program, in bytes of StableHLO
bytecode, at `chip_smoke.py`'s configuration (100 clients of
CIFAR-10-shaped data, `PaperCNN`, budget 10) or a smaller one.

A dataset closed over by the traced step would be lowered into the
program as constants and show here as hundreds of MB; taken as arguments
(`FLEngine.jit`) it adds nothing. Lowering only: nothing is compiled or
run, so the CPU backend gives the same count as a chip.

  PYTHONPATH=src JAX_PLATFORMS=cpu python benchmarks/program_size.py
  PYTHONPATH=src JAX_PLATFORMS=cpu python benchmarks/program_size.py \\
      --clients 8 --n-train 32 --n-val 16
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int,
                    default=chip_smoke.SIZES["n_clients"])
    ap.add_argument("--n-train", type=int,
                    default=chip_smoke.SIZES["n_train"])
    ap.add_argument("--n-val", type=int, default=chip_smoke.SIZES["n_val"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    chip_smoke.SIZES.update(n_clients=args.clients, n_train=args.n_train,
                            n_val=args.n_val, n_test=args.n_val)

    from jax._src.interpreters import mlir

    from repro.core import abstract_round_state, dpfl_round_step

    engine = chip_smoke.make_engine(args.seed)
    for repr_ in ("dense", "sparse"):
        cfg = chip_smoke.dpfl_config(args.seed, repr_)
        lowered = dpfl_round_step(engine, cfg).lower(
            abstract_round_state(engine, cfg))
        size = len(mlir.module_to_bytecode(lowered.compiler_ir()))
        print(f"{repr_}: clients={args.clients} n_train={args.n_train} "
              f"n_val={args.n_val} round_step_bytecode_bytes={size}")


if __name__ == "__main__":
    main()
