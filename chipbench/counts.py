"""Operations and bytes of the kernels on the stage-2 path, from the
configuration's own shapes. Every array is float32 (4 bytes) and the user
index int32, as the served path runs them.

``mari_matmul``: act(x (B, K) @ w (K, N) + acc_init) where the acc-init
rows are either one row per user of a stacked (U, N) table gathered by a
(B,) index, or a (B, N) block. ``gather_einsum``: the reparameterized DIN
activation unit's two contractions against a stacked per-user table,
``bd,uldh->blh`` (4-D table) and ``bl,uld->bd`` (3-D table).

K, N, L, D and H are the configuration's widths, not the kernel's padded
tiles: the kernel's own padding of K and N to its tiles is not counted.
The rows are those the kernel is handed, the batcher's bucket included:
rows that pad a pack to its bucket are work the kernel does, and their
waste is the batcher's, which ``rows_per_call`` and the stage-2 step's
``mfu`` (real candidates only) show. So a share of the roofline read
against these counts is that of the work the kernel was given.
"""
F32 = 4


def mari_matmul(rows: int, k: int, n: int, init_rows: int,
                gathered: bool) -> tuple[int, int]:
    """(FLOPs, bytes) of one call over ``rows`` candidate rows;
    ``init_rows`` is U for a gathered table, else the acc-init rows."""
    flops = 2 * rows * k * n
    nbytes = F32 * (rows * k + k * n + rows * n + init_rows * n)
    if gathered:
        nbytes += 4 * rows
    return flops, nbytes


def gather_einsum(rows: int, users: int, seq: int, d: int,
                  h: int | None) -> tuple[int, int]:
    """(FLOPs, bytes) of one call. ``h`` set: ``bd,uldh->blh``;
    ``h`` None: ``bl,uld->bd``."""
    if h is not None:
        flops = 2 * rows * seq * d * h
        nbytes = F32 * (rows * d + users * seq * d * h + rows * seq * h)
    else:
        flops = 2 * rows * seq * d
        nbytes = F32 * (rows * seq + users * seq * d + rows * d)
    return flops, nbytes + 4 * rows


def min_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound, against the bf16 peak (the chip's fastest matmul
    rate) and the HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
