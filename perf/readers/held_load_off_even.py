"""The worst expert layer's |picks on this share over the even router's - 1|, of the window's picks by layer and held expert."""


def read(facts):
    over_even = facts['counters'].get('held_picks_over_even')
    if not over_even:
        return None
    return max(abs(x - 1.0) for x in over_even)
