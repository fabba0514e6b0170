"""Paper Table 4/8 (lr tuning at the largest batch): tuning SSGD's lr down
lets it escape early traps, but DPSGD at full linear-scaled lr still wins."""
from __future__ import annotations

from .common import final_loss, parse_smoke, train_fc, write_table

LRS = (0.0625, 0.125, 0.25, 0.5)


def main(argv=None):
    smoke = parse_smoke(argv)
    steps = 24 if smoke else 120
    lrs = (LRS[1], LRS[3]) if smoke else LRS
    rows = []
    us = 0.0
    for lr in lrs:
        for algo in ("ssgd", "dpsgd"):
            r = train_fc(algo, lr, local_batch=400, steps=steps)
            us = r["us_per_step"]
            rows.append([algo, lr, final_loss(r["losses"])])
    write_table("table4_lr_tuning", ["algo", "lr", "final_loss"], rows)
    best_ssgd = min(r[2] for r in rows if r[0] == "ssgd")
    best_dpsgd = min(r[2] for r in rows if r[0] == "dpsgd")
    derived = (f"best ssgd={best_ssgd:.3f} (needs tuning) best dpsgd="
               f"{best_dpsgd:.3f} (paper T4: DPSGD best across lrs)")
    print(f"table4_lr_tuning,{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
