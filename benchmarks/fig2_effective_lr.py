"""Paper Fig. 2(a)+(b): DPSGD vs SSGD vs SSGD* at a large learning rate in
the large-batch setting, with the self-adjusting effective learning rate
alpha_e(t) and weight variance sigma_w^2(t) trajectories — and, new, the
landscape probe's Eq. 4 *prediction* alpha_e ~= alpha(1 - (alpha/2)
Tr(HC)/sigma_w^2) overlaid against the measured alpha_e (DESIGN §10)."""
from __future__ import annotations

from .common import final_loss, parse_smoke, train_fc, write_table

LR = 0.5
STEPS = 140


def main(argv=None):
    smoke = parse_smoke(argv)
    steps, every = (40, 10) if smoke else (STEPS, 20)
    rows = []
    runs = {}
    for algo in ("ssgd", "dpsgd", "ssgd_star"):
        r = train_fc(algo, LR, steps=steps, diag_every=every,
                     landscape_every=every)
        runs[algo] = r
        pred = {step: p for step, p in r["probes"]}
        for step, d in r["diags"]:
            p = pred.get(step)
            rows.append([algo, step, r["losses"][step - 1],
                         float(d.alpha_e), float(d.sigma_w_sq),
                         float(d.delta_s), float(d.delta_2),
                         float(p.alpha_e_pred) if p else float("nan"),
                         float(p.sharpness) if p else float("nan"),
                         float(p.trace_hc) if p else float("nan")])
    # SSGD* noise sensitivity.  Paper: only a finely tuned sigma0 converges;
    # at this 42k-param scale ALL sigmas converge (isotropic escape is
    # dimension-dependent) — honest negative, see EXPERIMENTS.md.
    star = {}
    for std in (0.1,) if smoke else (0.1, 0.01, 0.001):
        rs = train_fc("ssgd_star", LR, steps=steps, noise_std=std)
        star[std] = final_loss(rs["losses"])
        rows.append([f"ssgd_star(std={std})", steps, star[std],
                     float("nan"), float("nan"), float("nan"), float("nan"),
                     float("nan"), float("nan"), float("nan")])
    write_table("fig2_effective_lr",
                ["algo", "step", "loss", "alpha_e", "sigma_w_sq",
                 "delta_s", "delta_2", "alpha_e_pred", "sharpness",
                 "trace_hc"], rows)
    res = {a: final_loss(r["losses"]) for a, r in runs.items()}
    us = sum(r["us_per_step"] for r in runs.values()) / 3
    # Eq.4 fidelity: mean |pred - measured| / alpha over the DPSGD probes
    dp = runs["dpsgd"]
    pred = {s: p for s, p in dp["probes"]}
    errs = [abs(float(pred[s].alpha_e_pred) - float(d.alpha_e)) / LR
            for s, d in dp["diags"] if s in pred]
    eq4 = sum(errs) / len(errs) if errs else float("nan")
    derived = (f"final_loss ssgd={res['ssgd']:.3f} dpsgd={res['dpsgd']:.3f} "
               f"ssgd*={res['ssgd_star']:.3f}; eq4 |pred-meas|/alpha="
               f"{eq4:.3f}; ssgd* sweep "
               + " ".join(f"s{k}={v:.2f}" for k, v in star.items())
               + " (paper: DPSGD converges, SSGD fails; SSGD*-inferiority "
               "does not reproduce at 42k params — honest negative)")
    print(f"fig2_effective_lr,{us:.0f},{derived}")
    return res


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
