"""Host ms a step inside ``stream.stage`` (stream_stats()["stages"]) over the window's steps."""


def read(facts):
    st = facts['counters'].get('stream_stats') or {}
    steps = st.get('packed_steps', 0) + st.get('single_steps', 0)
    row = (st.get('stages') or {}).get('stream.stage')
    return 1e3 * row['busy_s'] / steps if row and steps else None
