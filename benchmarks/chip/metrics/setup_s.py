"""setup_s: process start to the window's first due arrival (weights,
compiles or compile-cache loads, warm-up), on the host clock."""


def read(cell):
    return cell.setup_s
