"""Paper Table 1: computational + memory overheads, Box-2D3R, c=8 tiles.

Reproduces the analytic cost model for the lower bound, TCStencil,
ConvStencil, LoRAStencil and SPTCStencil, and appends this repo's TPU-native
im2col-in-VMEM kernel (beyond-paper row). Values are per output point.
"""
from __future__ import annotations

from repro.core import analysis

PAPER = {          # (MACs, input access, param access) — paper Table 1
    "lower_bound": (49, 3.06, 0.77),
    "tcstencil": (286.72, 17.92, 17.92),
    "convstencil": (104, 13, 13),
    "lorastencil": (144, 4, 12),
    "sptcstencil": (56, 14, 7),
}


def rows(r: int = 3, c: int = 8):
    t = analysis.table1(r=r, c=c)
    out = []
    for name, cost in t.items():
        macs, inp, par = cost.as_tuple()
        ref = PAPER.get(name)
        ok = ""
        if ref:
            ok = "match" if (abs(macs - ref[0]) < 0.5 and
                             abs(inp - ref[1]) < 0.1 and
                             abs(par - ref[2]) < 0.1) else "MISMATCH"
        out.append((name, macs, inp, par, ok))
    return out


def main(csv: bool = True):
    print("# Table 1 — Box-2D3R per-point costs (paper §2.3 / §3.2.3)")
    print("method,macs,input_access,param_access,vs_paper")
    for name, macs, inp, par, ok in rows():
        print(f"{name},{macs:.2f},{inp:.2f},{par:.2f},{ok}")
    s = analysis.sptcstencil(3)
    for rival in ("tcstencil", "convstencil", "lorastencil"):
        ratio = analysis.METHODS[rival](3).macs / s.macs
        print(f"# MAC reduction vs {rival}: {ratio:.2f}x")
    print(f"# TPU im2col occupancy (K-pad): "
          f"{analysis.mxu_k_occupancy(3):.3f} of MXU lanes at K=49")


if __name__ == "__main__":
    from repro.compile_cache import use_checkout_cache
    use_checkout_cache()
    main()
