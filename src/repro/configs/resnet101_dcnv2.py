"""resnet101_dcnv2_bounded — ResNet-101 with modulated deformable
convolution (DCNv2, Zhu et al. 2019, arXiv:1811.11168) under this
paper's bounded receptive field.

ResNet-101 (He et al. 2016, arXiv:1512.03385): stages 3-4-23-3, widths
256/512/1024/2048, stem 64.  The last 29 3x3 convs are modulated DCLs
(c3's last 3, all 23 of c4, all 3 of c5), by the same "last N" rule
that gives ResNet-50 the 12 DCLs of the DCNv2 paper; mmdetection's
``stage_with_dcn`` would make all four c3 convs deformable (30).  The
Eq. 4 bound B=2 applies to the offsets, not to the masks, so every DCL
keeps ResNet-50's band, halo and receptive field.
"""
from repro.configs.resnet50_dcn import DET_SHAPES
from repro.models.registry import ArchSpec, register
from repro.models.resnet_dcn import ResNetDCNConfig

CONFIG = ResNetDCNConfig(
    name="resnet101_dcnv2_bounded",
    stage_sizes=(3, 4, 23, 3),
    widths=(256, 512, 1024, 2048),
    stem_width=64,
    num_dcn=29,
    offset_bound=2.0,
    num_classes=80,
    img_size=512,
    modulated=True,
)

register(ArchSpec(
    name="resnet101_dcnv2_bounded",
    family="cnn",
    config=CONFIG,
    shapes=dict(DET_SHAPES),
    source="arXiv:1811.11168 (DCNv2 in c3-c5) on ResNet-101 "
           "(arXiv:1512.03385), bounded as this paper's Sec. 3.1",
    notes="29 modulated DCLs, B=2: the bounded band kernels with a "
          "per-tap sigmoid mask; trains with use_kernel=False.",
))
