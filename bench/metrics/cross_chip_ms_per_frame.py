"""Device time of a sharded frame's cross-chip movement per frame served in
the traced window, averaged over the chips the cell uses: the modules
`jit_essr_shard_scatter` (a lane's patches from the frame's chip to the
shards) and `jit_essr_shard_gather` (the shards' HR outputs back to it).
A chip that enters one of these collectives before its peers counts its
wait inside it.

`phase_trace` reads these modules as phases where its phase names list
them; where they do not, it keeps them among each chip's largest unnamed
modules (`unnamed_modules`), and this reader takes them from there. None
where the program launches neither, as a one-chip program does."""
import re

import phase_trace

PHASE = re.compile(r"essr_shard_(scatter|gather)")


def read(ctx):
    red = phase_trace.for_ctx(ctx)
    if red is None or ctx["frames"] < 1:
        return None
    times = [t for chip in red["chips"]
             for name, t in [*chip["phase_s"].items(),
                             *((m[len("jit_"):], t)
                               for m, t in chip["unnamed_modules"])]
             if PHASE.fullmatch(name)]
    if not times:
        return None
    return 1e3 * sum(times) / ctx["chips"] / ctx["frames"]
