"""Paper reproduction driver: LeNet-5 + CGMQ on the synthetic digit set.

    PYTHONPATH=src python examples/lenet_cgmq.py --tier smoke \
        --direction dir1 --gran layer --bound 0.004

Tiers (see benchmarks/repro_tables.py): smoke | quick | paper. Training runs
on the unified scan-based engine (repro.train, DESIGN.md §9); ``--loop
python`` selects the per-batch reference loop (same numerics, slower), and
``--ckpt DIR``/``--resume`` checkpoint the full CGMQ TrainState so an
interrupted stage-4 run continues bit-identically.
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks.repro_tables import fp32_row, run_variant  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="smoke", choices=["smoke", "quick", "paper"])
    ap.add_argument("--direction", default="dir1",
                    choices=["dir1", "dir2", "dir3", "dir4"])
    ap.add_argument("--gran", default="layer", choices=["layer", "indiv"])
    ap.add_argument("--bound", type=float, default=0.004)
    ap.add_argument("--loop", default="scan", choices=["scan", "python"])
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir for the CGMQ stage (full TrainState)")
    ap.add_argument("--resume", action="store_true",
                    help="resume the CGMQ stage from --ckpt")
    ap.add_argument("--cgmq-epochs", type=int, default=None,
                    help="override the tier's CGMQ epoch count (e.g. stop a "
                         "run early, then --resume with the full count)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.resume and not args.ckpt:
        ap.error("--resume requires --ckpt")

    print(fp32_row(args.tier).fmt())
    row = run_variant(args.tier, args.direction, args.gran, args.bound,
                      log=print, loop=args.loop, ckpt_dir=args.ckpt,
                      resume=args.resume, cgmq_epochs=args.cgmq_epochs)
    print(row.fmt())
    if not row.satisfied:
        print("NOTE: cost constraint not yet satisfied at this tier's epoch "
              "budget — use a higher tier (the guarantee needs enough steps).")


if __name__ == "__main__":
    main()
