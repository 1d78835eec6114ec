"""`store.blocks_per_csum_batch` for a lone writer's cell: the
parked cell holds the unsuffixed name, and the arithmetic is its reader's."""
from perfbench.harness.manifest import load_reader

read = load_reader("store.blocks_per_csum_batch")
