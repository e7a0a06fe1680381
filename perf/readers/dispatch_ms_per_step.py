"""Host ms a step inside ``stream.dispatch`` and ``stream.dispatch_pack`` (stream_stats()["stages"])."""


def read(facts):
    st = facts['counters'].get('stream_stats') or {}
    steps = st.get('packed_steps', 0) + st.get('single_steps', 0)
    stages = st.get('stages')
    if not stages or not steps:
        return None
    busy = sum(stages.get(n, {}).get('busy_s', 0.0) for n in ('stream.dispatch', 'stream.dispatch_pack'))
    return 1e3 * busy / steps
