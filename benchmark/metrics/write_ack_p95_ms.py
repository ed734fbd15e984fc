"""Layer entry, the client's side of ``/query``: the 95th percentile of the
latency of the window's acknowledged writes (class ``add_film``, HTTP 200) —
what a catalogue team's loader waits for a film.  It holds the wait for the
exclusive side of the engine lock, the apply, the WAL and the refresh of the
arenas.  Nothing where the window acknowledged no write."""

import stats


def read(obs):
    lat = [r[4] - r[3] for r in obs.records if r[1] == "add_film" and r[5] == 200]
    return 1e3 * stats.percentile(lat, 95) if lat else None
