"""feeder_busy_s / wall_s of the window's stream (stream_stats())."""


def read(facts):
    st = facts['counters'].get('stream_stats') or {}
    return st['feeder_busy_s'] / st['wall_s'] if st.get('wall_s') else None
