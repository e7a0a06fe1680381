"""Host ms a step the feeder spent pulling the caller's iterator (``stream_stats()["waits"]["stream.source_wait"]``) over the window's steps."""


def read(facts):
    st = facts['counters'].get('stream_stats') or {}
    steps = st.get('packed_steps', 0) + st.get('single_steps', 0)
    row = (st.get('waits') or {}).get('stream.source_wait')
    return 1e3 * row['wait_s'] / steps if row and row.get('n') and steps else None
