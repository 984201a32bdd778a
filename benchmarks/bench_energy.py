"""Sec. VI reproduction — the ADC energy comparison against Rekhi et al.

    energy ratio = 2^(12.5-8) / 8 ~= 2.83x less ADC energy
    MACs/cycle   = 128 / 8       =  16x

Also sweeps the design space (tile, bits, gain) to emit the energy-per-MAC
frontier the paper's future-work section sketches.
"""

import itertools

from repro.core.energy import (
    ABFP_RESNET50,
    REKHI_RESNET50,
    AmsDesignPoint,
    energy_per_mac,
    paper_section6_comparison,
)
from repro.launch.compile_cache import enable_compile_cache


def run(csv_rows: list) -> dict:
    cmp = paper_section6_comparison()
    csv_rows.append(f"energy_vs_rekhi,0,x={cmp['adc_energy_reduction']:.2f}")
    csv_rows.append(f"macs_per_cycle,0,x={cmp['macs_per_cycle_gain']:.0f}")
    assert abs(cmp["adc_energy_reduction"] - 2.828) < 0.01
    assert cmp["macs_per_cycle_gain"] == 16.0

    frontier = {}
    for tile, bits, gain in itertools.product(
            (8, 32, 128), (6, 8, 10, 12.5), (1, 2, 4, 8, 16)):
        p = AmsDesignPoint(tile_width=tile, adc_bits=bits, gain=gain)
        frontier[(tile, bits, gain)] = energy_per_mac(p)
    # The paper's chosen point dominates Rekhi's on energy/MAC:
    assert energy_per_mac(ABFP_RESNET50) < energy_per_mac(REKHI_RESNET50)
    csv_rows.append(
        f"energy_per_mac_abfp,0,{energy_per_mac(ABFP_RESNET50):.1f}")
    csv_rows.append(
        f"energy_per_mac_rekhi,0,{energy_per_mac(REKHI_RESNET50):.1f}")
    return {"comparison": cmp,
            "frontier": {str(k): v for k, v in frontier.items()}}


if __name__ == "__main__":
    enable_compile_cache()
    rows: list = []
    out = run(rows)
    print("\n".join(rows))
    print(out["comparison"])
