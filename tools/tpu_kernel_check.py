#!/usr/bin/env python
"""Run the TPU-gated kernel tests on the chip.

The CPU test harness (tests/conftest.py) pins JAX to a virtual CPU mesh, so
the hardware proofs in tests/test_attention.py and tests/test_paged_kernel.py
are skipped there. This tool re-runs them with the real backend:

    python tools/tpu_kernel_check.py            # kernel tests only
    python tools/tpu_kernel_check.py -k gqa     # extra pytest args pass through

This parent never imports JAX: a chip belongs to one process, and the pytest
child is the one that needs it. Exit code is pytest's — 0 means every Pallas
kernel compiled via Mosaic and matched the jnp reference at every gated
shape. Without a TPU the child refuses to start (tests/conftest.py): a check
whose every test skipped must not read as green.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    env = dict(os.environ)
    env["TPUSC_TEST_ON_TPU"] = "1"  # tests/conftest.py skips the CPU pinning
    extra = sys.argv[1:]
    if not extra:
        # default: just the hardware-gated proofs. The interpret-mode tests'
        # 2e-5 tolerances are calibrated for CPU math and would spuriously
        # fail against the MXU's bf16-pass f32 matmuls.
        extra = ["-k", "on_tpu"]
    # -s: the gated tests print per-shape errors and timings — the output
    # must carry the measured magnitudes, not just PASS/FAIL.
    # test_paged_kernel.py carries the paged entries: the bring-up matrix
    # (decode x bf16/int8 x GQA group 1/2/3/4 x head_dim 64/128, verify x
    # group 1/2/4; compile + parity, and for decode at head 64 the refusal
    # by name with the gate's reference) and the kernel-vs-gather+einsum
    # timing at S in {4,16,32} full lanes and at the benchmark's steady
    # cell, 10 of 32 lanes active (printed, not asserted), the kernels on
    # the whole 5-D arena at a layer, and one decode chunk of the Mistral
    # cell's shape with the same live tokens at kv_arena_pages 512 and 4096
    # (a step's time must not follow the arena's size: asserted within 10 %),
    # and one of the OLMoE cell's shape at 2 live lanes of 32 against 32 of 32,
    # as the tree writes its KV rows and with every lane's rows written (PR 32:
    # the write follows the live lanes; at 2 lanes at least 0.3 ms a step less,
    # at 32 within 6 %), and (PR 34) the decode kernel over a PACKED arena at
    # the LFM2 cell's shape (8 KV heads of 64 stored as 4 pairs a 128-lane
    # row) at 1 / 6 / 16 / 32 live lanes of 32 against the gather + einsum
    # reference: `-k packed_on_tpu`, ~1 min. `layer/kv_write` a step of a chat
    # cell comes from a kept capture of a traced benchmark run read by
    # tools/trace_scopes.py (PERF.md section 5 has parent beside change).
    # test_olmoe.py carries the grouped expert kernel's rows at
    # OLMoE-1B-7B's widths: the grouped product with 25 / 64 of 64 experts
    # hit by 1, 4 and 48 rows each and with 63 experts empty, and the whole
    # layer at a decode step of 4 / 8 / 32 live lanes and prefills of 512 /
    # 2048 tokens, kernel against jax.lax.ragged_dot (ms, GB/s of the routed
    # experts' weights), and (PR 44) the layer as a PREFILL at the four expert
    # cells' widths (read from benchmark/configs/) and prompt buckets with the
    # row tile forced to 128 / 256 / 512, under a uniform router and one that
    # sends every token to one expert: ms a call, GB/s of the hit experts'
    # weights, parity with ragged_dot at every tile, the row the layer takes
    # marked `*`: `-k moe_prefill_tile_on_tpu`, ~10 min.
    # test_mla_moe.py carries the latent (MLA) decode kernel's rows at
    # Mistral-Small-4's shapes (32 heads over one 384-wide row, 16-token
    # pages, an arena of 16384 pages x 6 layers): parity with the gather +
    # einsum reference, ms, the share of the 640 B-a-token roofline, the same
    # attention with the row stored as two arrays, and the block sizes.
    # test_window_layers.py carries the window layers' rows at Mellum2's
    # shapes (32 query heads over 4 KV heads of 128, window 1024, a ring of
    # 65 pages a lane): the decode kernel with a first valid token at 1 / 6 /
    # 16 / 32 live lanes of 600-8000 tokens against the reference and against
    # a global call over the same lanes, and the windowed flash kernel at
    # 1024 / 4096 / 8192 tokens against the whole causal triangle:
    # `-k "window and on_tpu"`, ~2 min.
    # test_sambay_lm.py carries the rows of a differential, scanned model at
    # Phi-4-mini-flash's shapes (40 query heads of 64 over 10 rows of a KV
    # pair, E 5120, N 16): both softmax terms of every pair out of the global
    # and the window decode kernel (queries padded with zeros, sm_scale 1/8)
    # against two dense float64 softmaxes on the host, and the selective scan
    # (a bucket of 1024, the step over 32 lanes) against the recurrence in
    # float64: largest errors and times, `-k "sambay and on_tpu"`, ~1.5 min.
    # test_olmo_hybrid_lm.py carries the gated delta rule's rows at
    # Olmo-Hybrid-7B's widths (30 heads of 96 / 192, bf16 operands, float32
    # state): ``delta_step`` over 16 lanes and ``delta_chunked`` over a bucket
    # of 2048 (``real_len`` 1500) against the step iterated: largest errors
    # of the output and of the state, `-k "delta_rule_on_tpu"`, ~1 min.
    # test_kda_moe_lm.py carries the same rule with a decay a CHANNEL at
    # Solar-Open2's widths (64 heads of 128 / 128): the live-lane step on 8 of
    # 32 lanes (us a lane a layer beside the 8.5 MB a lane the state's bytes
    # allow) and, since PR 50, the chunked form through its kernel
    # (``delta_channel_chunk_kernel``) AND through the block form over buckets
    # of 2048 (1500 real) and 8192 (6000 real) against the step iterated,
    # decays down to 0.05 a step: largest errors and ms a layer side by side,
    # `-k "kda_rule_on_tpu"`, ~2 min.
    # Both files carry (PR 52) the one-token step's KERNEL rows
    # (``delta_step_kernel``, what ``delta_step_live`` runs on the chip) beside
    # the loop it replaced there (the gate forced shut) at 1, 3, 4, 5 and 8
    # live lanes of 32 / 16 at the two cells' widths
    # (``tests/test_delta_step_kernel.step_rows_on_tpu``): us a layer and a
    # live lane against the 10.4 / 5.4 the state's bytes allow, the largest
    # error of the output and of the state against the float64 step, every
    # other slice bit for bit: `-k "on_tpu_step_kernel"`, ~1 min (also matched
    # by `-k kda_rule_on_tpu` / `-k delta_rule_on_tpu`).
    cmd = [
        sys.executable, "-m", "pytest",
        os.path.join(REPO, "tests", "test_attention.py"),
        os.path.join(REPO, "tests", "test_paged_kernel.py"),
        os.path.join(REPO, "tests", "test_olmoe.py"),
        os.path.join(REPO, "tests", "test_mla_moe.py"),
        os.path.join(REPO, "tests", "test_window_layers.py"),
        os.path.join(REPO, "tests", "test_sambay_lm.py"),
        os.path.join(REPO, "tests", "test_olmo_hybrid_lm.py"),
        os.path.join(REPO, "tests", "test_kda_moe_lm.py"),
        "-v", "-rs", "-s", "--no-header",
        *extra,
    ]
    print("+", " ".join(cmd), flush=True)
    return subprocess.call(cmd, env=env, cwd=REPO)


if __name__ == "__main__":
    sys.exit(main())
