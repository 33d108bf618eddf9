"""Operations and bytes the cumulant-sums UDA needs for one request.

For each selected tuple (p, v) and each order j = 1..K the UDA adds
v^j kappa_j(p), where kappa_j is the Bernoulli cumulant, a polynomial of
degree j in p: Horner's rule takes 2j operations, the running power of v
one multiplication, the product one, the sum one, so 2j + 3 operations.
Over K orders: K (K + 1) + 3K operations a tuple.

Bytes: each selected tuple's p and v (f32), and its group id when the
aggregate is grouped, read once; each output group's K f32 terms written
once.  Masked rows, padding and the kernel's grid are not counted.
"""


def ops_bytes(rows: int, groups: int, orders: int, grouped: bool) -> tuple:
    ops = rows * (orders * (orders + 1) + 3 * orders)
    nbytes = rows * (12 if grouped else 8) + groups * orders * 4
    return float(ops), float(nbytes)
