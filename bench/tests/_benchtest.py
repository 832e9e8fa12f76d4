"""Shared by the benchmark's CPU tests."""

# A CPU rehearsal names the chip whose peak table it borrows; nothing
# it measures is a device number and none is written anywhere.
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


# The tiny configuration's own limits, where its readings differ from
# the published widths' (readings on the CPU): at DCL widths of 16 to 64
# channels int8 lies further from float32 (served vs fp32 reference
# 0.18 to 0.29; int4 control 0.73 to 0.86), and a shallower net carries
# the three-pass control's rounding less far (float32 served 1.1e-5 to
# 2.0e-5; control 5.9e-4 to 6.5e-4).  The other limits are the
# published configuration's.
TINY_LIMITS = {"fp32_l2": 0.5, "fp32_max": 1e-4}


def tiny(cfg: dict) -> dict:
    """The same family at a size the CPU runs in seconds: one DCL at
    stride 1 and two at stride 2, 64x64 images."""
    limits = {k: TINY_LIMITS.get(k, v) for k, v in cfg["limits"].items()}
    return dict(cfg, stage_sizes=[1, 2, 1, 1], widths=[32, 64, 128, 256],
                num_dcn=3, num_classes=8, img_size=64, bucket=64,
                limits=limits)
