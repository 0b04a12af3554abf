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
    # test_laguna_moe_lm.py carries (PR 53) the shared kernels at Laguna-S-2.1's
    # GQA groups, which no accepted cell runs: the paged decode kernel at 48 /
    # 8 / 128 (group 6) and the window decode kernel at 72 / 8 / 128 (group 9:
    # the first group that spills over one 8-row sublane tile without filling
    # a second), window 512, 16-token pages, 1 to 32 live lanes of 2k-17k
    # tokens, each beside group 8 (the accepted shape) against the gather +
    # einsum reference and the HBM roofline, and both flash kernels at 48 and
    # 72 heads over 2048 / 8192 / 16384 tokens against the reference and the
    # bf16 roofline: `-k "laguna and on_tpu"`, ~4 min. WHAT THEY LED TO (my chip
    # run, PR 53): Mosaic takes groups 6 and 9 as the kernels stand (compiled
    # for a described v5e before any chip call, then run: no refusal), and no
    # group is padded. Global decode at 1 / 4 / 16 live lanes: group 6 61.2 /
    # 72.4 / 73.7 % of the HBM roofline, group 8 60.7 / 72.8 / 73.7, group 9
    # 55.9 / 70.5 / 71.8; window decode at 1 / 4 / 16 / 32: group 6 16.8 / 36.4
    # / 47.4 / 49.9, group 8 20.0 / 38.8 / 44.0 / 50.7, group 9 18.5 / 35.1 /
    # 44.2 / 49.2: every row within a fifth of group 8's share, so
    # `_paged_decode_call` pads nothing and no model code knows the group
    # (padding 9 to 16 rows would only add MXU work to a memory-bound call).
    # The flash kernels read the same share at 48 and 72 heads (41.7 / 53.9 /
    # 25.7 % of the bf16 roofline at 2048 / 8192 / 16384 tokens, the window one
    # 21.5 / 21.0 / 6.7): what falls at 16384 is the STREAMED variant
    # (`flash_variant`: K and V of 16384 x 128 x 2 B pass
    # `KV_RESIDENT_LIMIT_BYTES`), at any head count (PERF.md section 7).
    # After review the file also carries the ROUTED half of the cell's expert
    # layer at its own shapes (`moe_experts`, top 10 of 256 by sigmoid scores
    # times 2.5, 32 experts of 3072 x 1024 held from 0 and from 96, a decode
    # step of 2 / 4 / 32 live lanes and prefills of 2048 and 6144 real rows)
    # against a float32 reference that shares no code with it (every held
    # expert applied to every row, weighted by the row's gate or by zero):
    # `-k "laguna_share_experts_on_tpu"`, ~3 min. WHAT IT READ (my chip run,
    # PR 53): the grouped kernel taken at every shape (`pallas tm=128`, 256 at
    # the prefills), largest error 0.0023-0.0056 under the limit of 0.02 where
    # no routed output lies 0.76-1.57 away and the share one place round
    # 1.32-2.03; the three routing counters equal the reference's counts; a
    # step of 2 lanes neither of which chose an expert held here answers
    # exactly zero. A step's call 0.154 ms at 4 live rows (5 experts hit, 611
    # GB/s of their weights), 0.367 at 32 (23 hit, 1183 GB/s); a prefill of
    # 2048 rows 3.85 ms, of 6144 in an 8192 bucket 17.3 ms: the sort and the
    # gather of all `rows x 10` assignments, of which an eighth land here
    # (PERF.md section 7, (c)).
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
        os.path.join(REPO, "tests", "test_laguna_moe_lm.py"),
        "-v", "-rs", "-s", "--no-header",
        *extra,
    ]
    print("+", " ".join(cmd), flush=True)
    return subprocess.call(cmd, env=env, cwd=REPO)


if __name__ == "__main__":
    sys.exit(main())
