"""Share of ``wall_s`` the dispatcher spent blocked on an empty staged queue (stream_stats()["waits"])."""


def read(facts):
    st = facts['counters'].get('stream_stats') or {}
    if 'stages' not in st or not st.get('wall_s'):
        return None
    wait = (st.get('waits') or {}).get('stream.dispatch_get_wait', {}).get('wait_s', 0.0)
    return 100.0 * wait / st['wall_s']
