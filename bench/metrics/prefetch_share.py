"""Share of the ``engine.assemble`` spans in the window that were made
while the previous batch ran (attr ``prefetched`` true), in %. A
program whose assembly spans carry no ``prefetched`` attr reads
nothing."""


def read(run):
    flags = [e.attrs["prefetched"] for e in run.spans
             if e.name == "engine.assemble" and "prefetched" in e.attrs]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
