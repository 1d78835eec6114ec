"""CRUSH placement for ceph_tpu.

CRUSH computes data placement as a pure function of (map, rule, x) — no lookup
service on the data path (reference: src/crush/mapper.c:900 crush_do_rule; see
SURVEY.md §1 "placement is computed, not looked up").  That purity is what makes it a
TPU kernel: bulk remaps evaluate the same map over thousands-to-millions of
independent x values (SURVEY.md §3.4).

Modules
-------
hashfn      rjenkins1 32-bit hashes (scalar oracle + numpy batch).
ln_table    the 2^44*log2 fixed-point tables, generated from their defining math
            plus the frozen upstream quirks needed for bit-exact placements.
types       CrushMap / Bucket / Rule / tunables model.
builder     map construction (crush/builder.c analog) + convenience topologies.
mapper_ref  exact scalar mapping oracle (crush/mapper.c semantics).
mapper_jax  batched placement engine over x on TPU (ops.crush_kernel).
"""

from .types import (
    CRUSH_BUCKET_UNIFORM,
    CRUSH_BUCKET_LIST,
    CRUSH_BUCKET_TREE,
    CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    CRUSH_ITEM_UNDEF,
    Bucket,
    CrushMap,
    Rule,
    RuleStep,
    Tunables,
    RULE_TAKE,
    RULE_CHOOSE_FIRSTN,
    RULE_CHOOSE_INDEP,
    RULE_CHOOSELEAF_FIRSTN,
    RULE_CHOOSELEAF_INDEP,
    RULE_EMIT,
)
from .hashfn import crush_hash32, crush_hash32_2, crush_hash32_3, crush_hash32_4, crush_hash32_5
from .mapper_ref import crush_do_rule, crush_ln
from .builder import (
    build_flat_map, build_skewed_two_level_map, build_two_level_map)

__all__ = [
    "CRUSH_BUCKET_UNIFORM", "CRUSH_BUCKET_LIST", "CRUSH_BUCKET_TREE",
    "CRUSH_BUCKET_STRAW", "CRUSH_BUCKET_STRAW2",
    "CRUSH_ITEM_NONE", "CRUSH_ITEM_UNDEF",
    "Bucket", "CrushMap", "Rule", "RuleStep", "Tunables",
    "RULE_TAKE", "RULE_CHOOSE_FIRSTN", "RULE_CHOOSE_INDEP",
    "RULE_CHOOSELEAF_FIRSTN", "RULE_CHOOSELEAF_INDEP", "RULE_EMIT",
    "crush_hash32", "crush_hash32_2", "crush_hash32_3", "crush_hash32_4",
    "crush_hash32_5", "crush_do_rule", "crush_ln",
    "build_flat_map", "build_skewed_two_level_map", "build_two_level_map",
]
