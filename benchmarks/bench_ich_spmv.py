"""Kernel-schedule benchmark: iCh-banded tile width vs fixed widths on the
Table-1 matrices. Metric = slot efficiency (useful nnz / padded R*W slots):
the TPU analogue of the paper's chunk-size tuning problem — too-wide tiles
waste MXU slots on padding, too-narrow tiles split rows into many segments
(per-tile dispatch overhead). Run standalone:

  PYTHONPATH=src python -m benchmarks.bench_ich_spmv
"""
import numpy as np

from repro.core import workloads as WL
from repro.core.tiling import build_schedule, ich_tile_width, pack_csr


def main(n=20000):
    print("matrix,ich_W,ich_eff,ich_tiles,best_fixed_W,best_fixed_eff,naive_max_eff")
    rows = []
    for spec in WL.TABLE1:
        nnz_rows = WL.matrix_row_nnz(spec, n).astype(np.int64)
        indptr = np.concatenate([[0], np.cumsum(nnz_rows)])
        nnz = int(indptr[-1])
        indices = np.zeros(nnz, np.int32)
        data = np.ones(nnz, np.float32)

        TILE_OVERHEAD = 64  # slot-equivalents per tile (grid-step dispatch)

        def eff(width):
            sched = build_schedule(nnz_rows, rows_per_tile=8, width=width)
            vals, _ = pack_csr(indptr, indices, data, sched)
            slots = vals.shape[0] * vals.shape[1] * vals.shape[2]
            cost = slots + TILE_OVERHEAD * vals.shape[0]
            return nnz / cost, vals.shape[0], sched.width

        wi = ich_tile_width(nnz_rows)
        e_ich, t_ich, _ = eff(wi)
        fixed = {w: eff(w)[0] for w in (8, 16, 32, 64, 128, 256, 512)}
        wb = max(fixed, key=fixed.get)
        # naive: width = max row nnz (no row splitting needed)
        e_naive, _, _ = eff(int(min(max(nnz_rows), 512)))
        print(f"{spec.name},{wi},{e_ich:.3f},{t_ich},{wb},{fixed[wb]:.3f},{e_naive:.3f}")
        rows.append((e_ich, fixed[wb], e_naive))
    a = np.asarray(rows)
    print(f"MEAN,,{a[:,0].mean():.3f},,,{a[:,1].mean():.3f},{a[:,2].mean():.3f}")


if __name__ == "__main__":
    main()
