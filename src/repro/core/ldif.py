"""LDIF serialization and LDAP-style search filters.

The paper's GRIS publishes storage metadata "in a suitable format (for
example, LDIF)" and the broker "uses the application ClassAd to build
specialized LDAP search queries", later converting "data, represented in
LDAP format, into ClassAds" (§6: "we have, in fact, developed primitive
libraries to achieve the conversion of this attribute set").

This module is those primitive libraries:

  * :func:`dumps` / :func:`loads` — LDIF text ↔ entry dicts,
  * :class:`Filter` / :func:`parse_filter` — an RFC 4515-style search
    filter language ``(&(availableSpace>=5368709120)(objectClass=...))``
    with ``&``, ``|``, ``!``, ``=``, ``>=``, ``<=``, presence ``=*`` and
    substring ``=ab*cd`` matching,
  * :func:`entry_to_classad` / :func:`classad_to_entry` — the LDIF↔ClassAd
    conversion the paper calls "not cumbersome and worth the effort".
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple, Union

from .classads import ClassAd, Expr, ListExpr, Literal, parse as parse_expr

__all__ = [
    "Entry",
    "dumps",
    "loads",
    "Filter",
    "parse_filter",
    "FilterSyntaxError",
    "entry_to_classad",
    "classad_to_entry",
    "policy_sources",
]

#: An LDAP entry: attribute → value or list of values. ``dn`` is an attribute.
Entry = Dict[str, Any]


# ---------------------------------------------------------------------------
# LDIF text format
# ---------------------------------------------------------------------------


def _format_value(v: Any) -> str:
    if isinstance(v, bool):
        return "TRUE" if v else "FALSE"
    if isinstance(v, float):
        return repr(v)
    return str(v)


def dumps(entries: Iterable[Entry]) -> str:
    """Serialize entries to LDIF text. ``dn`` is emitted first; multi-valued
    attributes repeat the attribute line, per LDIF."""
    blocks: List[str] = []
    for entry in entries:
        lines: List[str] = []
        if "dn" in entry:
            lines.append(f"dn: {entry['dn']}")
        for k, v in entry.items():
            if k == "dn":
                continue
            values = v if isinstance(v, (list, tuple)) else [v]
            for item in values:
                lines.append(f"{k}: {_format_value(item)}")
        blocks.append("\n".join(lines))
    return "\n\n".join(blocks) + ("\n" if blocks else "")


_NUM_RE = re.compile(r"^-?\d+$")
_FLOAT_RE = re.compile(r"^-?(\d+\.\d*|\.\d+|\d+)([eE][-+]?\d+)?$")


def _parse_value(s: str) -> Any:
    if s == "TRUE":
        return True
    if s == "FALSE":
        return False
    if _NUM_RE.match(s):
        return int(s)
    if _FLOAT_RE.match(s):
        try:
            return float(s)
        except ValueError:  # pragma: no cover
            return s
    return s


def loads(text: str) -> List[Entry]:
    """Parse LDIF text into entry dicts (typed: ints/floats/bools restored).

    Repeated attributes accumulate into lists; line continuations (leading
    space) are honoured.
    """
    entries: List[Entry] = []
    current: Optional[Entry] = None
    # unfold continuations
    unfolded: List[str] = []
    for line in text.splitlines():
        if line.startswith(" ") and unfolded:
            unfolded[-1] += line[1:]
        else:
            unfolded.append(line)
    for line in unfolded:
        if not line.strip():
            if current:
                entries.append(current)
                current = None
            continue
        if line.lstrip().startswith("#"):
            continue
        if ":" not in line:
            raise ValueError(f"malformed LDIF line: {line!r}")
        k, _, v = line.partition(":")
        k = k.strip()
        v = _parse_value(v.strip())
        if current is None:
            current = {}
        if k in current:
            prev = current[k]
            if isinstance(prev, list):
                prev.append(v)
            else:
                current[k] = [prev, v]
        else:
            current[k] = v
    if current:
        entries.append(current)
    return entries


# ---------------------------------------------------------------------------
# LDAP search filters (RFC 4515 subset)
# ---------------------------------------------------------------------------


class FilterSyntaxError(ValueError):
    pass


@dataclass(frozen=True)
class Filter:
    """A parsed LDAP search filter node."""

    op: str  # '&' | '|' | '!' | '=' | '>=' | '<=' | 'present' | 'substr'
    children: Tuple["Filter", ...] = ()
    attr: str = ""
    value: Any = None

    def matches(self, entry: Mapping[str, Any]) -> bool:
        op = self.op
        if op == "&":
            return all(c.matches(entry) for c in self.children)
        if op == "|":
            return any(c.matches(entry) for c in self.children)
        if op == "!":
            return not self.children[0].matches(entry)

        # attribute comparisons: case-insensitive key lookup; multi-valued
        # attributes match if ANY value matches (LDAP semantics).
        low = self.attr.lower()
        found = None
        for k, v in entry.items():
            if k.lower() == low:
                found = v
                break
        if found is None:
            return False
        values = found if isinstance(found, (list, tuple)) else [found]

        if op == "present":
            return True
        for v in values:
            if op == "=" and _eq(v, self.value):
                return True
            if op == ">=" and _cmp_ge(v, self.value):
                return True
            if op == "<=" and _cmp_le(v, self.value):
                return True
            if op == "substr" and _substr(v, self.value):
                return True
        return False

    def attributes(self) -> List[str]:
        """All attribute names referenced by this filter (for GRIS
        projection — the broker requests only 'the attributes of
        interest')."""
        out: List[str] = []
        if self.attr:
            out.append(self.attr)
        for c in self.children:
            out.extend(c.attributes())
        return out

    def __str__(self) -> str:
        if self.op in ("&", "|"):
            return "(%s%s)" % (self.op, "".join(map(str, self.children)))
        if self.op == "!":
            return "(!%s)" % self.children[0]
        if self.op == "present":
            return f"({self.attr}=*)"
        if self.op == "substr":
            return f"({self.attr}={'*'.join(self.value)})"
        return f"({self.attr}{self.op}{_format_value(self.value)})"


def _coerce_pair(a: Any, b: Any) -> Optional[Tuple[Any, Any]]:
    an = isinstance(a, (int, float)) and not isinstance(a, bool)
    bn = isinstance(b, (int, float)) and not isinstance(b, bool)
    if an and bn:
        return float(a), float(b)
    if an or bn:
        # one side numeric, other string: try to coerce the string
        try:
            return float(a), float(b)
        except (TypeError, ValueError):
            return None
    return str(a).lower(), str(b).lower()


def _eq(a: Any, b: Any) -> bool:
    pair = _coerce_pair(a, b)
    return pair is not None and pair[0] == pair[1]


def _cmp_ge(a: Any, b: Any) -> bool:
    pair = _coerce_pair(a, b)
    return pair is not None and pair[0] >= pair[1]


def _cmp_le(a: Any, b: Any) -> bool:
    pair = _coerce_pair(a, b)
    return pair is not None and pair[0] <= pair[1]


def _substr(value: Any, parts: Sequence[str]) -> bool:
    s = str(value).lower()
    pos = 0
    for i, part in enumerate(parts):
        p = part.lower()
        if not p:
            continue
        j = s.find(p, pos)
        if j < 0:
            return False
        if i == 0 and parts[0] and j != 0:
            return False
        pos = j + len(p)
    if parts and parts[-1] and not s.endswith(parts[-1].lower()):
        return False
    return True


class _FParser:
    def __init__(self, text: str):
        self.text = text
        self.i = 0

    def error(self, msg: str) -> FilterSyntaxError:
        return FilterSyntaxError(f"{msg} at {self.i} in {self.text!r}")

    def parse(self) -> Filter:
        f = self.parse_filter()
        if self.i != len(self.text.strip()):
            # allow trailing whitespace only
            if self.text[self.i :].strip():
                raise self.error("trailing input")
        return f

    def parse_filter(self) -> Filter:
        self._skip_ws()
        if self.i >= len(self.text) or self.text[self.i] != "(":
            raise self.error("expected '('")
        self.i += 1
        self._skip_ws()
        ch = self.text[self.i] if self.i < len(self.text) else ""
        if ch in "&|":
            self.i += 1
            children = []
            self._skip_ws()
            while self.i < len(self.text) and self.text[self.i] == "(":
                children.append(self.parse_filter())
                self._skip_ws()
            self._expect(")")
            if not children:
                raise self.error("empty composite filter")
            return Filter(ch, tuple(children))
        if ch == "!":
            self.i += 1
            child = self.parse_filter()
            self._skip_ws()
            self._expect(")")
            return Filter("!", (child,))
        # simple: attr OP value
        m = re.match(r"([A-Za-z_][A-Za-z0-9_.;-]*)\s*(>=|<=|=)", self.text[self.i :])
        if not m:
            raise self.error("expected attribute comparison")
        attr, op = m.group(1), m.group(2)
        self.i += m.end()
        # value: up to the matching close paren
        depth = 0
        j = self.i
        while j < len(self.text):
            c = self.text[j]
            if c == "(":
                depth += 1
            elif c == ")":
                if depth == 0:
                    break
                depth -= 1
            j += 1
        if j >= len(self.text):
            raise self.error("unterminated filter")
        raw = self.text[self.i : j].strip()
        self.i = j + 1  # consume ')'
        if op == "=":
            if raw == "*":
                return Filter("present", attr=attr)
            if "*" in raw:
                return Filter("substr", attr=attr, value=tuple(raw.split("*")))
            return Filter("=", attr=attr, value=_parse_value(raw))
        return Filter(op, attr=attr, value=_parse_value(raw))

    def _skip_ws(self) -> None:
        while self.i < len(self.text) and self.text[self.i].isspace():
            self.i += 1

    def _expect(self, ch: str) -> None:
        if self.i >= len(self.text) or self.text[self.i] != ch:
            raise self.error(f"expected {ch!r}")
        self.i += 1


def parse_filter(text: str) -> Filter:
    """Parse an RFC 4515-style LDAP search filter."""
    return _FParser(text).parse()


# ---------------------------------------------------------------------------
# LDIF ↔ ClassAd conversion (the paper's "primitive libraries")
# ---------------------------------------------------------------------------

#: Attributes whose LDIF string values are ClassAd *expressions*, not data.
#: The paper's ``requirements`` policy attribute is the canonical case.
_EXPR_ATTRS = {"requirements", "rank"}


@lru_cache(maxsize=512)
def _parse_expr_cached(src: str) -> Expr:
    """Parsed policy expressions, memoized: a grid's GRIS entries repeat a
    handful of distinct ``requirements``/``rank`` sources thousands of
    times (expression trees are immutable, so sharing is safe)."""
    return parse_expr(src)


def entry_to_classad(entry: Mapping[str, Any], *, expr_attrs: Optional[set] = None) -> ClassAd:
    """Convert an LDIF entry into a ClassAd (Match Phase step 1).

    Scalar values become literals; the ``requirements`` / ``rank`` strings
    are parsed as ClassAd expressions so site policy survives conversion.
    ``dn`` and ``objectClass`` ride along as plain string attributes.

    This sits on the GRIS hot path (every flattened-view row of every
    snapshot build), so the common scalar cases skip ``__setitem__``'s
    isinstance ladder and populate the ad's slots directly; anything
    exotic falls back to the full assignment path.
    """
    exprs = _EXPR_ATTRS if expr_attrs is None else expr_attrs
    ad = ClassAd()
    attrs = ad._attrs
    spelling = ad._spelling
    for k, v in entry.items():
        kl = k.lower()
        tv = v.__class__
        if tv is str:
            e = _parse_expr_cached(v) if kl in exprs else Literal(v)
        elif tv is int or tv is float or tv is bool:
            e = Literal(v)
        elif tv is list or tv is tuple:
            e = ListExpr(tuple(x if isinstance(x, Expr) else Literal(x) for x in v))
        else:
            ad[k] = v  # Expr / ClassAd / None: full __setitem__ dispatch
            continue
        attrs[kl] = e
        spelling[kl] = k
    return ad


def policy_sources(entries: Sequence[Mapping[str, Any]]) -> List[Optional[str]]:
    """Each entry's usage-policy source: the ``repr`` of the ``requirements``
    expression that ``entry_to_classad(entry)`` carries, or None, without
    making the ad. The key matches case-insensitively, the last spelling
    winning, as a ClassAd's keys do; its value is converted alone by
    ``entry_to_classad``, so the two cannot disagree."""
    # a grid's rows share a few key sequences and a few policy strings: find
    # the key's spelling once for each sequence, convert each string once
    spelling: Dict[Tuple[str, ...], Optional[str]] = {}
    by_text: Dict[str, str] = {}
    out: List[Optional[str]] = []
    for e in entries:
        keys = tuple(e)
        if keys in spelling:
            key = spelling[keys]
        else:
            key = spelling[keys] = next(
                (k for k in reversed(keys) if k.lower() == "requirements"), None
            )
        if key is None:
            out.append(None)
            continue
        val = e[key]
        if val.__class__ is str:
            src = by_text.get(val)
            if src is None:
                src = by_text[val] = repr(entry_to_classad({key: val})["requirements"])
        else:
            src = repr(entry_to_classad({key: val})["requirements"])
        out.append(src)
    return out


def classad_to_entry(ad: ClassAd, *, dn: Optional[str] = None) -> Entry:
    """Convert a ClassAd back to an LDIF entry. Expression-valued attributes
    are serialized as their source form; evaluated literals as values."""
    entry: Entry = {}
    if dn is not None:
        entry["dn"] = dn
    for k, expr in ad.items():
        if isinstance(expr, Literal) and not isinstance(expr.value, ClassAd):
            v = expr.value
            entry[k] = list(v) if isinstance(v, list) else v
        else:
            entry[k] = repr(expr)
    return entry
