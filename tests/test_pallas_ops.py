"""Pallas kernel correctness vs the XLA reference implementation.

Runs in interpreter mode on the CPU test platform. The references, the
cases and the tolerances live in ``aigw_tpu/ops/pallas/parity.py`` —
the chip smoke's kernels child runs the same checks compiled on the
TPU; ``tests/test_pallas_tpu_aot.py`` keeps every kernel lowering
through Mosaic without a chip. The W8A16 matmul's cases are in
``tests/test_qmatmul.py``."""

import jax.numpy as jnp
import pytest

from aigw_tpu.ops.pallas import parity


# -- ragged prefill kernel (ISSUE 6) -------------------------------------

class TestRaggedPrefillKernel:
    """Interpret-mode parity for the ragged paged-attention prefill
    (one program for any batch geometry) vs a dense numpy reference —
    packed mixed-length sequences, q blocks spanning sequence
    boundaries, misaligned offset-resumed starts, GQA."""

    def _run(self, lens, starts, page_size, q_block, H, Hkv, D,
             n_pages, dtype=jnp.float32, rtol=parity.F32_TOL):
        parity.check_ragged(lens, starts, page_size, q_block, H, Hkv, D,
                            n_pages, dtype=dtype, tol=rtol,
                            interpret=True)

    def test_small_mixed_lengths_f32(self):
        # q blocks span sequence boundaries; one empty-adjacent short seq
        self._run(lens=[3, 12, 7, 20], starts=[0, 0, 0, 0],
                  page_size=8, q_block=16, H=4, Hkv=2, D=32, n_pages=16)

    def test_offset_resumed_misaligned_starts(self):
        # nonzero, page-misaligned resume offsets (prefix-cache partial
        # hit / chunked continuation shapes)
        self._run(lens=[5, 9, 14], starts=[3, 8, 21],
                  page_size=8, q_block=8, H=4, Hkv=4, D=32, n_pages=24)

    def test_tiny_moe_geometry_mixed_lengths(self):
        # tiny-moe attention geometry (ISSUE 18): H=4, Hkv=2 (GQA
        # GROUP=2 divides heads), D=16, 16-token pages — the ragged
        # program the MoE family admits through now that the
        # family-fallback row is gone; one offset-resumed sequence
        self._run(lens=[7, 30, 13], starts=[0, 5, 0],
                  page_size=16, q_block=16, H=4, Hkv=2, D=16,
                  n_pages=16)

    @pytest.mark.slow

    def test_production_shape_mixed_lengths(self):
        # llama-3-8B attention geometry (H=32, Hkv=8, D=128, 128-token
        # pages) at the ISSUE's canonical mixed-length admission burst,
        # one sequence resuming at a misaligned offset (the chip smoke
        # runs this case compiled at the served geometry)
        self._run(lens=[7, 86, 301, 1024], starts=[0, 37, 0, 128],
                  page_size=128, q_block=128, H=32, Hkv=8, D=128,
                  n_pages=48, dtype=jnp.bfloat16, rtol=5e-2)
