"""Beyond-paper: rank sharding configurations by prediction, not execution.

The paper selects the fastest blocked algorithm by predicting each
candidate from per-kernel models (§4.5).  At cluster scale the candidates
are *sharding strategies* of one (arch × shape) cell and the "model" is
the three-term roofline of each candidate's compiled dry-run: compiling
takes seconds, executing each candidate on 256 chips is what this avoids.

    PYTHONPATH=src python examples/distributed_config_search.py \
        [--arch deepseek-7b] [--shape train_4k]

NOTE: needs the 512-device dry-run environment; this script sets the
XLA host-device flag itself and must run as a fresh process.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse     # noqa: E402
import sys          # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.dryrun import lower_cell                  # noqa: E402
from repro.perf.predictor import ConfigCandidate, rank_configs  # noqa: E402
from repro.perf.roofline import TARGET_KIND, RooflineTerms  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--shape", default="train_4k")
    args = ap.parse_args()

    def build(strategy, remat):
        def fn():
            _, meta = lower_cell(args.arch, args.shape, strategy=strategy,
                                 remat_policy=remat, verbose=False)
            return RooflineTerms(
                flops=meta["flops"], bytes_accessed=meta["bytes"],
                coll_bytes=meta["coll_bytes"],
                n_devices=meta["n_devices"],
                model_flops=meta["model_flops"], device_kind=TARGET_KIND)
        return fn

    candidates = [
        ConfigCandidate("tp (Megatron TP+FSDP)", build("tp", None)),
        ConfigCandidate("dp (pure DP + ZeRO-3)", build("dp", None)),
        ConfigCandidate("dp + dots-remat", build("dp", "dots"),
                        note="memory > HBM on v5e; see EXPERIMENTS §Perf"),
    ]
    print(f"== ranking sharding configs for {args.arch} x {args.shape} "
          f"(16x16 mesh) by compiled-dry-run prediction ==")
    ranked = rank_configs(candidates, extract=lambda x: x)
    for r in ranked:
        t = r.terms
        print(f"   {r.name:24s} predicted step {t.bound_s * 1e3:8.0f} ms "
              f"(compute {t.compute_s * 1e3:6.0f} / memory "
              f"{t.memory_s * 1e3:6.1f} / collective "
              f"{t.collective_s * 1e3:6.0f}) dominant={t.dominant}"
              + (f"  [{r.note}]" if r.note else ""))
    print(f"selected: {ranked[0].name}")
    print("distributed_config_search OK")


if __name__ == "__main__":
    main()
