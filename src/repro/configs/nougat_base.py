"""Nougat-base (Blecher et al., arXiv:2308.13418; ``facebook/nougat-base``),
AdaParse's expensive parser: a Swin encoder over the 896x672 page image
(patch 4, embed 128, depths 2/2/14/2, heads 4/8/16/32, 7x7 windows) and
a 10-layer mBART decoder (d 1024, 16 heads, FFN 4096, 50,000 tokens,
4,096 learned positions) that cross-attends to the encoder's 588 last
tokens.

Shapes: training (page image -> text cross-entropy), the serve encode
step (a chunk of pages through the encoder into cross-attention K/V)
and the serve decode step (one token for every page slot)."""
from repro.configs.base import ArchConfig, NougatConfig, ShapeConfig, register

NOUGAT_SHAPES = (
    ShapeConfig("train_pages", "train", {"global_batch": 32, "dec_len": 1024},
                note="pages per step; teacher-forced cross-entropy"),
    ShapeConfig("parse_encode", "serve", {"global_batch": 8},
                note="one encode chunk of pages into cross-attention K/V"),
    ShapeConfig("parse_decode", "decode", {"global_batch": 96},
                note="one decode token for every page slot"),
)


def published() -> NougatConfig:
    return NougatConfig(name="nougat-base")


def tiny() -> NougatConfig:
    """Every mechanism of the published model at a CPU size: two stages
    with a merge, unshifted and shifted blocks with the wrap mask on a
    grid larger than the window, the relative bias, two decoder
    layers, a cache read in several blocks (pages of up to 384
    tokens, as ``serve``'s corpus has) and slots for several encode
    chunks."""
    return NougatConfig(
        name="nougat-tiny", image_hw=(64, 96), patch=4, embed_dim=16,
        depths=(2, 2), heads=(2, 4), window=4, dec_layers=2,
        dec_d_model=32, dec_heads=4, dec_d_ff=64, vocab_size=96,
        max_positions=512, glyph_cell=(8, 12), decode_slots=16,
        cache_len=384, param_dtype="float32", compute_dtype="float32")


@register("nougat-base")
def config() -> ArchConfig:
    return ArchConfig(
        arch_id="nougat-base", family="nougat", model=published(),
        shapes=NOUGAT_SHAPES, source="paper (Nougat, arXiv:2308.13418)",
        reduced=lambda: ArchConfig(
            arch_id="nougat-base", family="nougat", model=tiny(),
            shapes=NOUGAT_SHAPES, source="reduced"),
    )
