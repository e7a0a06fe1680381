"""Bytes staged host to device a sample, from the staged arrays' shapes."""


def read(facts):
    n = facts['counters'].get('h2d_bytes')
    return n / facts['window']['samples'] if n and facts['window']['samples'] else None
