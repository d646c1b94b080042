"""txn_s: transactions decided in the window over the window's wall time
(host clock). A decided transaction is a committed New-Order, a New-Order
refused for stock, a Payment or an Order-Status; a delivered order counts a
tenth, one TPC-C Delivery serving a warehouse's ten districts. Stock-Levels
ride every round but are not counted: the program never computes their
answer."""


def read(rec):
    if not rec.rounds or rec.window_s <= 0:
        return None
    return rec.decided() / rec.window_s
