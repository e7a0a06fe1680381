"""Steps dispatched inside K-step packs over all steps of the window (stream_stats())."""


def read(facts):
    st = facts['counters'].get('stream_stats') or {}
    done = st.get('packed_steps', 0) + st.get('single_steps', 0)
    return st['packed_steps'] / done if done else None
