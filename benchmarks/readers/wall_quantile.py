"""A quantile of the walls of the window's calls or requests (harness or
client clock), by linear interpolation between the sorted walls."""


def read(ctx, q):
    walls = sorted(ctx["file_walls"])
    if not walls:
        return None
    at = q * (len(walls) - 1)
    lo = int(at)
    hi = min(lo + 1, len(walls) - 1)
    return walls[lo] + (walls[hi] - walls[lo]) * (at - lo)
