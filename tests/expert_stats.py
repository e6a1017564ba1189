"""What every expert family's decode-step test asserts of the grouped
product's tile counts (``glm5next.MOE_STAT_NAMES``' last two)."""
from generativeaiexamples_tpu.models import glm5next


def assert_one_live_row_tiles(stat_names, stats, cfg, slots):
    """``stats``: a decode step's counts by name, ONE row live of
    ``slots``. The row holds a pair an expert at most, so the grid ran a
    16-row tile an expert hit (``sum(ceil(sizes / tm))``), and the plan
    sized ``ceil(slots k / 16) + held`` tiles an expert layer."""
    assert tuple(stat_names[:len(glm5next.MOE_STAT_NAMES)]) == glm5next.MOE_STAT_NAMES
    layers = stats["moe_experts_held"] // cfg.experts_held
    assert stats["moe_tiles_used"] == stats["moe_experts_hit"] <= stats["moe_tiles_planned"]
    assert stats["moe_tiles_planned"] == stats["moe_experts_held"] + layers * -(-slots * cfg.num_experts_per_tok // 16)
