"""Child of ``test_sharded.py``: the four-chip cell rehearsed on four
virtual CPU devices with the XLA lowering (the Pallas interpreter cannot
run inside ``shard_map``).  ``fault`` leaves the halo exchange between
devices out (each device gets its own tail back in place of its
neighbour's).  Prints the result object as its last line."""
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parent)]

if __name__ == "__main__":
    import jax

    if "fault" in sys.argv[1:]:
        jax.lax.ppermute = lambda x, axis_name, perm: x
    from rehearse import rehearse

    out = rehearse("l1_core_x4", 2**31 + 5, 3.0, False,
                   dict(num_cases=1500), dict(row_group_rows=2048))
    print(json.dumps(out))
