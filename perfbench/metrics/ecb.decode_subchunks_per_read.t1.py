"""Sub-chunks the OSDs' decodes rebuilt per read acknowledged in the
window: stripes x sub-chunks a chunk x chunks rebuilt, summed over the
submissions (counter `ec_decode_subchunks`; one sub-chunk a chunk for
Reed-Solomon).  With 2 of 12 OSDs down a read loses 2 x 8 / 12 data
chunks on average, so a Clay 8+4 read of 4 MiB (128 stripes of 64
sub-chunks a chunk) reads about 128 x 64 x 4 / 3 = 10,923; a program
that cut the sub-chunks would show here.  Nothing on a program without
the counter."""
from perfbench.harness.readers import window_ops


def read(r):
    if "osd.ec_decode_subchunks" not in r.after:
        return None
    ops = window_ops(r)
    return r.delta("osd.ec_decode_subchunks") / ops if ops else None
