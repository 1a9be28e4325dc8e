"""Roofline table assembly: reads the dry-run JSONs (launch/dryrun.py) and
prints the per-(arch x shape x mesh) three-term table for EXPERIMENTS.md,
plus the analytic HBM-traffic model of the Shotgun kernel variants
(DESIGN §4.4)."""
from __future__ import annotations

import json
import pathlib

RESULTS = pathlib.Path(__file__).resolve().parent / "results" / "dryrun"

# v4-class TPU used for the per-round analytic model
HBM_GBPS = 1200e9
MXU_FLOPS = 275e12
ICI_GBPS = 45e9     # per-link ICI bandwidth — floor for the Δz merge time


def shotgun_round_model(n, d, K, block=128, a_bytes=4, fused_single=None):
    """Per-round HBM bytes / flops / roofline time of the two rounds.

    scalar       P=K·block gathered columns; O(1) flops/byte.
    fused        single launch; z/r/g/delta stay in VMEM.  In single-phase
                 mode (one sample tile) each A block streams ONCE per round;
                 whether (n, d) gets single-phase is decided by the kernel's
                 own VMEM heuristic unless overridden.
    """
    if fused_single is None:
        from repro.kernels.shotgun_block import auto_tile_n
        fused_single = auto_tile_n(n, block, d=d) == n
    P = K * block
    a_blk = n * block * a_bytes
    vec = n * 4
    rows = {}
    rows["scalar"] = {"bytes": P * n * a_bytes + 3 * vec,
                      "flops": 4 * P * n}
    rows["fused"] = {"bytes": (1 if fused_single else 2) * K * a_blk,
                     "flops": 4 * K * block * n}
    for name, r in rows.items():
        r["intensity"] = r["flops"] / r["bytes"]
        r["t_mem_us"] = r["bytes"] / HBM_GBPS * 1e6
        r["t_flops_us"] = r["flops"] / MXU_FLOPS * 1e6
        r["bound"] = "memory" if r["t_mem_us"] > r["t_flops_us"] else "compute"
    return rows


# VPU flop-equivalents charged per transcendental (exp / sigmoid / log1p
# chain of the stable logistic tile) — coarse, but the point of the model
# is that even at 8x a madd the term is O(n) against O(K·block·n) madds
TRANS_FLOPS = 8


def logistic_round_model(n, d, K, block=128, a_bytes=4, newton=False,
                         fused_single=None):
    """Logistic twin of ``shotgun_round_model`` (DESIGN §12).

    HBM traffic is IDENTICAL to the squared-loss round: the loss seam swaps
    the residual tile computed from the VMEM-resident margin, and the margin
    z (plus y, which already streams in for the objective) is all the
    logistic tile reads.  What changes is flops:

      * every kernel pays one stable-sigmoid/log1p chain per resident
        sample per round (TRANS_FLOPS · n) for the residual r = -y σ(-y z)
        and the log1p objective tile;
      * the fused Newton variant (Bian et al.) additionally squares the
        already-fetched A tile and accumulates the per-block curvature
        h_b = Σ_i a_ib² σ_i(1-σ_i) — 2·K·block·n madd-class flops, zero
        extra bytes (the (n,1) weight scratch and (K,block) accumulator
        live in VMEM, see ``fused_vmem_bytes(loss=)``).

    So the logistic round is *more* compute-dense at the same traffic, and
    the memory-bound verdict of the lasso model can only tighten — the loss
    seam is roofline-free.
    """
    rows = shotgun_round_model(n, d, K, block=block, a_bytes=a_bytes,
                               fused_single=fused_single)
    for name, r in rows.items():
        r["flops"] += TRANS_FLOPS * n
        if newton and name == "fused":
            r["flops"] += 2 * K * block * n
        r["intensity"] = r["flops"] / r["bytes"]
        r["t_flops_us"] = r["flops"] / MXU_FLOPS * 1e6
        r["bound"] = ("memory" if r["t_mem_us"] > r["t_flops_us"]
                      else "compute")
    return rows


def logistic_table(shapes=((8192, 256, 2), (1024, 2048, 4))):
    out = [f"{'kernel':16s} {'n':>6s} {'d':>6s} {'K':>3s} {'GB/round':>10s} "
           f"{'flops/B':>8s} {'t_mem_us':>9s} {'bound':>7s}"]
    for (n, d, K) in shapes:
        for newton in (False, True):
            tag = "_newton" if newton else ""
            for name, r in logistic_round_model(n, d, K,
                                                newton=newton).items():
                if newton and name != "fused":
                    continue
                out.append(f"{name + tag:16s} {n:6d} {d:6d} {K:3d} "
                           f"{r['bytes'] / 1e9:10.6f} "
                           f"{r['intensity']:8.1f} "
                           f"{r['t_mem_us']:9.3f} {r['bound']:>7s}")
    return "\n".join(out)


def sparse_round_model(n, d, K, tile, block=128, R=8, val_bytes=4):
    """Per-round HBM bytes/flops of the fused Block-Shotgun round on a
    dense design vs a BlockedCSC one (DESIGN §8).  Sparse tiles carry both
    int32 row indices and values ((4 + ``val_bytes``) B/slot — 8 for f32
    vals, 6 for bf16 vals via ``BlockedCSC.astype``); the dense round
    streams whole (n × block) column blocks.  The fused sparse round
    (DESIGN §8.3) fetches each selected block's nnz tiles ONCE per round
    (one grid step serves both gather and scatter) and keeps z/Δz/r/x in
    VMEM for all ``R`` rounds of a launch, so the z/x vector traffic is
    amortized over R and the per-launch constant (z0/y in, z/x out, x0 in)
    is all that remains.  Also reports the at-rest design-matrix footprint
    — the paper-scale constraint that motivates the container.
    """
    dense = shotgun_round_model(n, d, K, block=block)["fused"]
    d_pad = -(-d // block) * block
    vec = n * 4
    slot = 4 + val_bytes                         # int32 row + stored value
    # one (tile × block) rows+vals fetch per block per round; the
    # per-launch z0/y input + z output (3 n-vectors) and the two full-
    # width x transfers (x0 in, x out — 2·d_pad) amortize over R rounds.
    fu_bytes = K * tile * block * slot + (3 * vec + 2 * d_pad * 4) / R
    fu_flops = 2 * 2 * K * tile * block          # madd per nnz, each phase
    fused = {"bytes": fu_bytes, "flops": fu_flops,
             "intensity": fu_flops / fu_bytes,
             "t_mem_us": fu_bytes / HBM_GBPS * 1e6}
    return {
        "dense": dense, "sparse_fused": fused,
        "hbm_bytes_ratio_fused": dense["bytes"] / fu_bytes,
        "storage_bytes_dense": 4 * n * d,
        "storage_bytes_bcsc": slot * tile * d_pad,
    }


def sharded_merge_model(n, merge_rounds=1, scheme="none", topk_frac=0.01,
                        inner=1):
    """Per-round wire bytes of the distributed solver's Δz merge (DESIGN
    §3/§7): one n-vector all-reduce per ``merge_rounds`` rounds, optionally
    compressed (``dist.compression.wire_bytes`` accounting) and/or
    hierarchical (the slow inter-pod hop carries 1/``inner`` of the bytes).
    """
    import numpy as np
    from repro.dist.compression import wire_bytes
    per_merge = wire_bytes({"dz": np.zeros(n, np.float32)}, scheme,
                           topk_frac=topk_frac)
    return {
        "wire_bytes_per_merge": per_merge,
        "wire_bytes_per_round": per_merge / merge_rounds,
        "slow_hop_bytes_per_round": per_merge / merge_rounds / inner,
        # ICI-bandwidth floor on the merge's wall time — bench_sharded uses
        # it to keep the exposed-wire accounting positive when the measured
        # sync/async difference drowns in host-emulation timing noise
        "wire_us_per_merge": per_merge / ICI_GBPS * 1e6,
    }


def sharded_wire_table(n=2048, schemes=("none", "bf16", "int8", "topk")):
    out = [f"{'scheme':8s} {'merge':>6s} {'B/merge':>10s} {'B/round':>10s} "
           f"{'slow hop/round (inner=4)':>24s}"]
    for scheme in schemes:
        for merge_rounds in (1, 8):
            m = sharded_merge_model(n, merge_rounds, scheme, topk_frac=0.01,
                                    inner=4)
            out.append(f"{scheme:8s} {merge_rounds:6d} "
                       f"{m['wire_bytes_per_merge']:10.0f} "
                       f"{m['wire_bytes_per_round']:10.1f} "
                       f"{m['slow_hop_bytes_per_round']:24.1f}")
    return "\n".join(out)


def shotgun_table(shapes=((1024, 2048, 4), (2048, 8192, 4))):
    out = [f"{'kernel':12s} {'n':>6s} {'d':>6s} {'K':>3s} {'GB/round':>10s} "
           f"{'flops/B':>8s} {'t_mem_us':>9s} {'bound':>7s}"]
    for (n, d, K) in shapes:
        for name, r in shotgun_round_model(n, d, K).items():
            out.append(f"{name:12s} {n:6d} {d:6d} {K:3d} "
                       f"{r['bytes'] / 1e9:10.6f} {r['intensity']:8.1f} "
                       f"{r['t_mem_us']:9.3f} {r['bound']:>7s}")
    return "\n".join(out)


def load(tag="final"):
    rows = []
    for p in sorted(RESULTS.glob(f"*__{tag}.json")):
        rows.append(json.loads(p.read_text()))
    return rows


def fmt_table(rows, mesh="single"):
    out = []
    hdr = (f"{'arch':24s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'collect_s':>10s} {'bottleneck':>11s} {'useful':>7s}")
    out.append(hdr)
    for r in rows:
        if r.get("mesh") != mesh:
            continue
        if r["status"] == "skip":
            out.append(f"{r['arch']:24s} {r['shape']:12s} {'SKIP':>10s}")
            continue
        if r["status"] != "ok":
            out.append(f"{r['arch']:24s} {r['shape']:12s} {'ERROR':>10s}")
            continue
        t = r["terms"]
        out.append(
            f"{r['arch']:24s} {r['shape']:12s} {t['compute_s']:10.3e} "
            f"{t['memory_s']:10.3e} {t['collective_s']:10.3e} "
            f"{r['bottleneck'][:-2]:>11s} "
            f"{r.get('useful_flops_ratio', 0):7.3f}")
    return "\n".join(out)


def run():
    print(shotgun_table(), flush=True)
    print(logistic_table(), flush=True)
    print(sharded_wire_table(), flush=True)
    rows = load("final")
    for mesh in ("single", "multi"):
        n_ok = sum(1 for r in rows if r.get("mesh") == mesh and r["status"] == "ok")
        n_skip = sum(1 for r in rows if r.get("mesh") == mesh and r["status"] == "skip")
        n_err = sum(1 for r in rows if r.get("mesh") == mesh and r["status"] == "error")
        print(f"roofline,{mesh},ok={n_ok},skip={n_skip},err={n_err}", flush=True)
    print(fmt_table(load("opt"), "single"))
    return rows


if __name__ == "__main__":
    run()
