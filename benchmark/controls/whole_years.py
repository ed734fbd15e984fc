"""Control ``whole_years``: the reference that keeps every film of the date
bound's year — what a lossy year index returns where the recheck of the
bound's own token row is skipped.  Put in the program's place on the filter
cell, the comparison has to call it not correct: the bound falls inside its
year, so films of that year released before it come back."""

import reference_filter


class _WholeYears(reference_filter.FilterReference):
    def keeps(self, films, genre, date):
        year = int(date.split("-")[0])
        return (self.g.film_genre[films] == genre) & (self.g.film_date[films, 0] >= year)


def walker(world):
    return _WholeYears(world.g)
