"""bench.py record fields that come from what TRACED, not from argv."""

import argparse


def test_attn_bwd_record_uses_traced_choice_not_argv():
    """Advisor (round 5): records must carry the backward kernel that actually
    TRACED; argv disagreements get flagged instead of silently logged."""
    import bench  # stdlib-only top level: importing it never initializes jax

    from distributed_sigmoid_loss_tpu.ops import pallas_short_attention as psa

    def args(attn_bwd="loop"):
        return argparse.Namespace(attn_bwd=attn_bwd)

    psa.reset_traced_bwd_batch_heads()
    try:
        # Requested batched but nothing ever traced → flagged, never a clean tag.
        f = bench._attn_bwd_record_fields(args("batched"))
        assert f["attn_bwd_mismatch"] is True
        assert f["attn_bwd_traced"] == "none"

        # Step traced BEFORE the set_bwd_batch_heads flip: per-head loop ran.
        psa._TRACED_BWD_BATCH_HEADS.add(False)
        f = bench._attn_bwd_record_fields(args("batched"))
        assert f["attn_bwd"] == "loop"  # the truth, not argv
        assert f["attn_bwd_argv"] == "batched"
        assert f["attn_bwd_mismatch"] is True

        # Consistent run: traced choice matches argv, clean tag only.
        psa.reset_traced_bwd_batch_heads()
        psa._TRACED_BWD_BATCH_HEADS.add(True)
        assert bench._attn_bwd_record_fields(args("batched")) == {
            "attn_bwd": "batched"
        }

        # Default loop traced as loop: no extra record fields at all.
        psa.reset_traced_bwd_batch_heads()
        psa._TRACED_BWD_BATCH_HEADS.add(False)
        assert bench._attn_bwd_record_fields(args()) == {}
    finally:
        psa.reset_traced_bwd_batch_heads()
