"""The longest single ``stream.dispatch`` / ``stream.dispatch_pack`` call of the window, in ms."""


def read(facts):
    stages = (facts['counters'].get('stream_stats') or {}).get('stages')
    if not stages:
        return None
    longest = [stages[n]['max_s'] for n in ('stream.dispatch', 'stream.dispatch_pack') if n in stages]
    return 1e3 * max(longest) if longest else None
