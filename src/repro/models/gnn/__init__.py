from repro.models.gnn.bundle import GraphBundle, build_bundle
from repro.models.gnn.layers import (gcn_conv, sage_conv, gin_conv, gat_conv,
                                     init_gcn, init_sage, init_gin, init_gat,
                                     sage_conv_block, gin_conv_block)
from repro.models.gnn.models import GNN_ARCHS, make_gnn

__all__ = ["GraphBundle", "build_bundle", "gcn_conv", "sage_conv",
           "gin_conv", "gat_conv", "init_gcn", "init_sage", "init_gin",
           "init_gat", "GNN_ARCHS", "make_gnn", "sage_conv_block",
           "gin_conv_block"]
