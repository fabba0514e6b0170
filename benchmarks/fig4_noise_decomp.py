"""Paper Fig. 4: decomposition of the DPSGD noise into the minibatch part
Delta_S and the landscape-dependent part Delta2; Delta2 >> Delta_S early and
decays as training smooths the landscape."""
from __future__ import annotations

from .common import parse_smoke, train_fc, write_table


def main(argv=None):
    steps = 30 if parse_smoke(argv) else 120
    r = train_fc("dpsgd", 0.5, steps=steps, diag_every=10)
    rows = [[step, float(d.delta_s), float(d.delta_2),
             float(d.sigma_w_sq), float(d.alpha_e)]
            for step, d in r["diags"]]
    write_table("fig4_noise_decomp",
                ["step", "delta_s", "delta_2", "sigma_w_sq", "alpha_e"], rows)
    early = rows[0]
    late = rows[-1]
    ratio_early = early[2] / max(early[1], 1e-20)
    derived = (f"delta2/deltaS early={ratio_early:.1f} "
               f"delta2 early={early[2]:.2e} late={late[2]:.2e} "
               "(paper: Delta2>>DeltaS early, decays)")
    print(f"fig4_noise_decomp,{r['us_per_step']:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
