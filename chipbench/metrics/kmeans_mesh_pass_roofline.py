"""Share of the least time of one k-means pass over all the cell's chips
that the programs reading the data achieve per iteration, in % (see
``chipbench/roofline.py``)."""

from chipbench.roofline import pass_share


def read(w):
    return pass_share(w, "kmeans_mesh")
