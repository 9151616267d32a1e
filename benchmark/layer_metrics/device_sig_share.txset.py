"""Share of the window's sets' signatures that reached the device: sum of
the program's `crypto.verify.dispatch.batch` histogram / signatures of
the sets handed over (%). 100 at a cold verify cache; `correct` holds it
there.

The reading is `device_sig_share.live`'s, made by that reader, in the cell
`txset-5000.validate`."""


def read(cell):
    return cell.spec.layer_reader("device_sig_share.live")(cell)
