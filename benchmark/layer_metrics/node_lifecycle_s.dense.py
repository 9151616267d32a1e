"""A node's own start and end per replay (s).

The reading is `node_lifecycle_s.catchup`'s, made by that reader, in the
cell `multisig-dense.dense-replay`."""


def read(cell):
    return cell.spec.layer_reader("node_lifecycle_s.catchup")(cell)
