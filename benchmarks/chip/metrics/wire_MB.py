"""Bytes the machines' collectives move per fit, in MB (10^6 bytes):
``ClusterResult.wire_bytes_total``, counted at trace time from each
collective's shapes (``core/comm.py``), averaged over the window's fits."""


def read(run):
    return sum(f.wire_bytes for f in run.fits) / len(run.fits) / 1e6
