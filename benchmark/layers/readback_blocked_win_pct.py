"""D2H and completer: share of the readback window (issue to fetch done,
`readback.window`) that a completer spent blocked in the fetch
(`readback.wait`), as a window delta; `readback_blocked_pct` is the same
ratio over the server's lifetime."""
from _lib import phase_total_ms


def read(ctx):
    window = phase_total_ms(ctx, "readback.window")
    return 100.0 * phase_total_ms(ctx, "readback.wait") / window if window else None
