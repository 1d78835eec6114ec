"""`kernels.digest_device_share` for a lone op's cell, write or read: the
parked cell holds the unsuffixed name, and the arithmetic is its reader's."""
from perfbench.harness.manifest import load_reader

read = load_reader("kernels.digest_device_share")
