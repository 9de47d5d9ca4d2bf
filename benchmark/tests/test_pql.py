"""What a keyed or sorted deployment sends: quoted strings as keyword
values in the query, keys beside ids in the reply."""

import pytest
from harness import check, pql


def test_a_quoted_string_is_a_keyword_value():
    call = pql.parse('GroupBy(Rows(seg), filter=Row(segment="gold"), '
                     'sort="count desc", limit=5)')
    assert call.kwargs["sort"] == "count desc" and call.kwargs["limit"] == 5
    assert call.kwargs["filter"].kwargs == {"segment": "gold"}


@pytest.mark.parametrize("q", [
    'Row(segment="gold)', "Row(segment='gold')", 'Row("gold")',
    'Row(segment="a\\"b")', 'Row(age > "4")'])
def test_anything_else_stays_an_error(q):
    with pytest.raises(ValueError):     # PqlError, or int() of a string
        pql.parse(q)


def test_replies_of_a_keyed_field_compare_by_key():
    assert check.canonical("TopN", [
        {"id": 7, "key": "gold", "count": 3}, {"id": 2, "count": 1}]) \
        == [("gold", 3), (2, 1)]
    assert check.canonical("GroupBy", [{"group": [
        {"field": "segment", "row_id": 7, "row_key": "gold"},
        {"field": "edu", "row_id": 1}], "count": 4, "agg": 9}]) \
        == {("gold", 1): (4, 9)}
