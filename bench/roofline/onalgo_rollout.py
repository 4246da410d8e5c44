"""Operations and bytes that OnAlgo's rollout needs, whatever kernel
implements it: a lower bound on the work, so that no correct
implementation can read above its roofline.

The kernel takes each slot's state index of every device and returns
each slot's decisions; in between it keeps, per device, the power dual
lam (float32, the configuration's precision) and the visit counts over
the M states, whole numbers up to the horizon T.  The bound charges the
least of the ways a kernel could keep that state:

* In VMEM, where the state fits packed: counts at the fewest bits that
  hold T (12 at T = 2048), lam at 4 bytes.  At N = 2^20, M = 73 that is
  119 MB, under a v5e's 128 MiB of VMEM (``peaks.json``), so a kernel
  could hold it for a whole launch: it is charged one read and one write
  of the packed state per launch of ``slab`` slots (the engine's launch
  length, pinned in the configuration).
* Streamed from HBM, where it does not fit: each slot reads lam and
  every count, and writes lam and the one count a slot increments.

Besides, each slot reads every device's state index (the fewest bits
that hold M states) and writes its decision (one bit).  The raw values
the kernel also takes today feed only the series' sums, which need not
be made inside a kernel, so they are not charged.  This bound is loose:
it leaves out everything today's kernel streams beyond that, and a
kernel at the bound would be far under its compute.

Operations: per device and state, the policy test lam o + mu h < w and
the two policy-weighted sums over the state distribution (o y rho and
h y rho): at least 4 multiply-or-add operations, counted as 4 flops
against the chip's highest peak.

Neither count depends on the kernel's tiling (block_n) or on how many
slots one grid step covers (chunk).
"""

TRACE_NAME = r"^%onalgo_tiled"  # the Pallas kernel's operation in the trace


def counts(N: int, M: int, T: int, slots: int, slab: int,
           vmem_bytes: float) -> dict:
    count_bits = int(T).bit_length()  # a count reaches T
    state = N * (M * count_bits / 8 + 4)  # packed counts and lam
    if state <= vmem_bytes:
        state_per_slot = 2 * state / slab
    else:
        state_per_slot = state + N * (4 + count_bits / 8)
    io_per_slot = N * ((int(M) - 1).bit_length() + 1) / 8
    return {"flops": 4.0 * N * M * slots,
            "bytes": (state_per_slot + io_per_slot) * slots}
