"""Paper Table 1 (CIFAR-10 batch-size scaling, proxied at CPU scale):
linear-scaling-rule lr for increasing total batch; SSGD vs DPSGD final loss,
plus the new closed-loop ``ssgd_autolr`` column (DESIGN §10): plain SSGD
whose LR multiplier is clamped online from probed sharpness — the explicit
version of DPSGD's implicit self-adjustment.  The scenario: SSGD+AutoLR
survives the large-batch LRs where SSGD diverges.

``run_cell`` is the per-(algo, batch_scale) unit benchmarks.matrix reuses
as its ``large_batch`` workload plugin — the AdaScale-style batch/LR
scaling axis of the sweep spec."""
from __future__ import annotations

from .common import final_loss, parse_smoke, train_fc, write_table

BASE_LOCAL, BASE_LR = 100, 0.125   # nB=500 baseline
SCALES = (1, 2, 4)                  # nB = 500, 1000, 2000
N = 5


def run_cell(algo: str, scale: int, *, steps: int = 120) -> dict:
    """One (algo, batch-scale) cell under the linear LR scaling rule."""
    r = train_fc(algo, BASE_LR * scale, local_batch=BASE_LOCAL * scale,
                 steps=steps)
    ctl = r["controller"]
    return {"algo": algo, "nB": N * BASE_LOCAL * scale,
            "lr": BASE_LR * scale, "final_loss": final_loss(r["losses"]),
            "autolr_scale": float(ctl.scale) if ctl is not None else 1.0,
            "us_per_step": r["us_per_step"]}


def main(argv=None):
    smoke = parse_smoke(argv)
    steps = 24 if smoke else 120
    scales = SCALES[::2] if smoke else SCALES   # keep baseline + largest
    rows = []
    us = 0.0
    for s in scales:
        for algo in ("ssgd", "dpsgd", "ssgd_autolr"):
            r = run_cell(algo, s, steps=steps)
            us = r["us_per_step"]
            rows.append([algo, r["nB"], r["lr"], r["final_loss"],
                         r["autolr_scale"]])
    write_table("table1_large_batch",
                ["algo", "nB", "lr", "final_loss", "autolr_scale"], rows)
    big = {r[0]: r[3] for r in rows if r[1] == N * BASE_LOCAL * scales[-1]}
    derived = (f"largest-batch loss ssgd={big['ssgd']:.3f} "
               f"dpsgd={big['dpsgd']:.3f} ssgd_autolr={big['ssgd_autolr']:.3f}"
               " (paper T1: DPSGD wins at bs=8192; AutoLR keeps SSGD alive)")
    print(f"table1_large_batch,{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
